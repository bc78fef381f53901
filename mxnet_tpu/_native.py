"""Loader for the native host runtime (src/ → libmxtpu_runtime.so).

Reference counterpart: ``python/mxnet/base.py _load_lib`` loading
libmxnet.so via ctypes. The library is built from ``src/`` on demand
(first import) with the baked-in g++ toolchain; set
``MXNET_TPU_NO_NATIVE=1`` to force the pure-Python fallbacks.
"""
from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading

_LIB = None
_LOCK = threading.Lock()
_SRC_FILES = ("common.cc", "engine.cc", "storage.cc", "recordio.cc",
              "mxtpu_runtime.h", "Makefile")


def _repo_root():
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lib_path():
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "lib", "libmxtpu_runtime.so")


def _src_digest(srcdir):
    """sha256 over the runtime's sources. Staleness is decided by
    content, not mtime: a checkout or a copied tree resets every mtime,
    and a git-ignored .so on disk then looks newer than sources it was
    never built from."""
    h = hashlib.sha256()
    for f in _SRC_FILES:
        h.update(f.encode() + b"\0")
        with open(os.path.join(srcdir, f), "rb") as fh:
            h.update(fh.read())
    return h.hexdigest()


def _stamp_path(lib):
    return lib + ".srchash"


def _needs_build(lib, srcdir):
    if not os.path.exists(lib):
        return True
    try:
        with open(_stamp_path(lib)) as fh:
            return fh.read().strip() != _src_digest(srcdir)
    except OSError:
        return True


def _build():
    srcdir = os.path.join(_repo_root(), "src")
    lib = _lib_path()
    if not os.path.isdir(srcdir):
        return None  # installed without sources; need a prebuilt lib
    if _needs_build(lib, srcdir):
        os.makedirs(os.path.dirname(lib), exist_ok=True)
        # Sweep temp files orphaned by builders killed mid-make (their
        # finally never ran). Only files older than 10 min are removed so
        # a concurrent live build's temp is never yanked out from under
        # its os.replace.
        import glob
        import time

        for stale in glob.glob(lib + ".tmp.*"):
            try:
                if time.time() - os.path.getmtime(stale) > 600:
                    os.remove(stale)
            except OSError:
                pass
        # Build to a per-process temp name and rename into place atomically:
        # tools/launch.py spawns N workers that may build concurrently, and
        # a reader must never dlopen a partially written .so.
        tmp = "%s.tmp.%d" % (lib, os.getpid())
        try:
            # single source of truth for flags: src/Makefile
            subprocess.run(["make", "-C", srcdir, "OUT=%s" % tmp],
                           check=True, capture_output=True)
            os.replace(tmp, lib)
            with open(tmp, "w") as fh:
                fh.write(_src_digest(srcdir) + "\n")
            os.replace(tmp, _stamp_path(lib))
        finally:
            if os.path.exists(tmp):
                os.remove(tmp)
    return lib


def _declare(lib):
    c = ctypes.c_void_p
    lib.MXTGetLastError.restype = ctypes.c_char_p
    lib.MXTEngineCreate.restype = c
    lib.MXTEngineCreate.argtypes = [ctypes.c_int]
    lib.MXTEngineFree.argtypes = [c]
    lib.MXTEngineNewVar.restype = ctypes.c_int64
    lib.MXTEngineNewVar.argtypes = [c]
    lib.MXTEnginePush.restype = ctypes.c_int
    lib.MXTEnginePush.argtypes = [
        c, ENGINE_FN, c,
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int, ctypes.c_int]
    lib.MXTEngineWaitForVar.restype = ctypes.c_int
    lib.MXTEngineWaitForVar.argtypes = [c, ctypes.c_int64]
    lib.MXTEngineWaitAll.restype = ctypes.c_int
    lib.MXTEngineWaitAll.argtypes = [c]
    lib.MXTEngineStats.argtypes = [c, ctypes.POINTER(ctypes.c_int64),
                                   ctypes.POINTER(ctypes.c_int64)]

    lib.MXTStoragePoolCreate.restype = c
    lib.MXTStoragePoolCreate.argtypes = [ctypes.c_size_t]
    lib.MXTStoragePoolFree.argtypes = [c]
    lib.MXTStorageAlloc.restype = c
    lib.MXTStorageAlloc.argtypes = [c, ctypes.c_size_t]
    lib.MXTStorageRelease.argtypes = [c, c, ctypes.c_size_t]
    lib.MXTStoragePoolStats.argtypes = [c] + [ctypes.POINTER(ctypes.c_int64)] * 4
    lib.MXTStoragePoolDrain.argtypes = [c]

    lib.MXTRecordIOWriterCreate.restype = c
    lib.MXTRecordIOWriterCreate.argtypes = [ctypes.c_char_p]
    lib.MXTRecordIOWriterWrite.restype = ctypes.c_int
    lib.MXTRecordIOWriterWrite.argtypes = [c, ctypes.c_char_p, ctypes.c_size_t]
    lib.MXTRecordIOWriterTell.restype = ctypes.c_int64
    lib.MXTRecordIOWriterTell.argtypes = [c]
    lib.MXTRecordIOWriterClose.restype = ctypes.c_int
    lib.MXTRecordIOWriterClose.argtypes = [c]
    lib.MXTRecordIOReaderCreate.restype = c
    lib.MXTRecordIOReaderCreate.argtypes = [ctypes.c_char_p]
    lib.MXTRecordIOReaderNext.restype = ctypes.c_int
    lib.MXTRecordIOReaderNext.argtypes = [
        c, ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_size_t)]
    lib.MXTRecordIOReaderSeek.restype = ctypes.c_int
    lib.MXTRecordIOReaderSeek.argtypes = [c, ctypes.c_int64]
    lib.MXTRecordIOReaderTell.restype = ctypes.c_int64
    lib.MXTRecordIOReaderTell.argtypes = [c]
    lib.MXTRecordIOReaderClose.restype = ctypes.c_int
    lib.MXTRecordIOReaderClose.argtypes = [c]
    return lib


ENGINE_FN = ctypes.CFUNCTYPE(None, ctypes.c_void_p)


def get_lib():
    """The loaded native library, or None (disabled / build failed)."""
    global _LIB
    if _LIB is not None:
        return _LIB or None
    with _LOCK:
        if _LIB is not None:
            return _LIB or None
        if os.environ.get("MXNET_TPU_NO_NATIVE", "0") == "1":
            _LIB = False
            return None
        try:
            lib = _build()
            if lib is None:
                _LIB = False
                return None
            _LIB = _declare(ctypes.CDLL(lib))
        except (OSError, subprocess.CalledProcessError) as e:
            # the pure-Python fallbacks take over, but never silently:
            # say once what failed, with the compiler's own words
            detail = getattr(e, "stderr", None) or b""
            logging.getLogger("mxnet_tpu").warning(
                "native runtime unavailable (%s); using the pure-Python "
                "fallbacks%s", e,
                "\n" + detail.decode(errors="replace")[-2000:]
                if detail else "")
            _LIB = False
            return None
    return _LIB or None


def last_error():
    lib = get_lib()
    if lib is None:
        return ""
    return (lib.MXTGetLastError() or b"").decode()
