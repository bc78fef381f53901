"""Device contexts.

Parity surface: ``include/mxnet/base.h:85-230`` (``struct Context`` with
``kCPU/kGPU/kCPUPinned/kCPUShared`` device types) and
``python/mxnet/context.py``. TPU-native design: a ``Context`` names a JAX
device (or, for sharded execution, a position in a mesh). ``mx.tpu(0)`` is
first-class; ``cpu(i)`` maps onto host-platform devices so that unit tests
can use N virtual CPU devices as distinct "chips"
(``--xla_force_host_platform_device_count``), mirroring the reference's
multi-CPU-context test pattern (SURVEY §4).
"""
from __future__ import annotations

import threading

from .base import MXNetError

_DEVTYPE_IDS = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 5, "tpu": 6}
_DEVID_TYPES = {v: k for k, v in _DEVTYPE_IDS.items()}


class Context:
    """A device context. Immutable, hashable, usable as a `with` scope."""

    _default_ctx = threading.local()
    devtype2str = _DEVID_TYPES
    devstr2type = _DEVTYPE_IDS

    __slots__ = ("device_type", "device_id", "_old_ctx")

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_type = device_type.device_type
            self.device_id = device_type.device_id
        else:
            if isinstance(device_type, int):
                device_type = _DEVID_TYPES[device_type]
            if device_type not in _DEVTYPE_IDS:
                raise MXNetError("unknown device type %r" % (device_type,))
            self.device_type = device_type
            self.device_id = int(device_id)
        self._old_ctx = None

    @property
    def device_typeid(self):
        return _DEVTYPE_IDS[self.device_type]

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    def __str__(self):
        return self.__repr__()

    def __enter__(self):
        self._old_ctx = getattr(Context._default_ctx, "value", None)
        Context._default_ctx.value = self
        return self

    def __exit__(self, ptype, value, trace):
        Context._default_ctx.value = self._old_ctx
        return False

    # -- JAX device resolution ------------------------------------------------
    def jax_device(self):
        """Resolve to a concrete jax.Device, or raise naming what exists.

        cpu→'cpu' backend devices (virtual multi-device under
        xla_force_host_platform_device_count); tpu→'tpu' backend;
        gpu→'gpu' backend, else 'tpu' (parity alias: "accelerator i").
        The one substitution: when the platform list was explicitly
        forced to cpu (``JAX_PLATFORMS=cpu`` / ``jax_platforms``), tpu(i)
        and gpu(i) mean host device i, so scripts written for mx.tpu()
        run in the CPU test suite. JAX's own "TPU init failed, falling
        back to CPU" is not that case and raises here.
        """
        if self.device_type in ("cpu", "cpu_pinned", "cpu_shared"):
            backend = "cpu"
        elif self.device_type == "gpu" and _backend_devices("gpu"):
            backend = "gpu"
        else:
            backend = "tpu"
        devs = _backend_devices(backend)
        if not devs and backend != "cpu" and _platform_forced_to_cpu():
            devs = _backend_devices("cpu")
        if not 0 <= self.device_id < len(devs):
            import jax

            raise MXNetError(
                "context %r: this process has %d %s device(s); the default "
                "backend is %r with %s and jax_platforms=%r"
                % (self, len(devs), backend, jax.default_backend(),
                   [str(d) for d in jax.local_devices()],
                   _jax_platforms()))
        return devs[self.device_id]

    def empty_cache(self):
        """Parity: mx.context.Context.empty_cache — XLA manages HBM; no-op."""

    @classmethod
    def default_ctx(cls):
        ctx = getattr(cls._default_ctx, "value", None)
        return ctx if ctx is not None else cpu()


_DEVICE_CACHE = {}
_DEVICE_CACHE_LOCK = threading.Lock()


def _backend_devices(platform):
    """This process's addressable devices of one platform, () when the
    platform is absent (multi-host: jax.devices() includes other
    workers' devices, which cannot be NDArray homes)."""
    with _DEVICE_CACHE_LOCK:
        if platform not in _DEVICE_CACHE:
            import jax

            try:
                _DEVICE_CACHE[platform] = tuple(
                    jax.local_devices(backend=platform))
            except RuntimeError:
                _DEVICE_CACHE[platform] = ()
        return _DEVICE_CACHE[platform]


def _jax_platforms():
    """The platform list JAX was given (``JAX_PLATFORMS`` or
    ``jax.config.update("jax_platforms", ...)``), None when unset."""
    import jax

    return jax.config.jax_platforms


def _platform_forced_to_cpu():
    """True when the JAX platform list names cpu and nothing else. A TPU
    host's ``tpu,cpu`` is not a forced cpu."""
    names = [p.strip() for p in (_jax_platforms() or "").split(",")
             if p.strip()]
    return names == ["cpu"]


def kernel_platform():
    """The platform Pallas kernels are built for: ``"tpu"`` compiles
    with Mosaic; ``"cpu"`` (the test suite) runs interpret mode and the
    jnp reference paths. Any other default backend raises — interpret
    mode must never be what runs on an accelerator."""
    import jax

    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise MXNetError(
            "Pallas kernels target tpu (Mosaic) or cpu (interpret mode); "
            "the default JAX backend is %r" % backend)
    return backend


def device_record():
    """The device fields every benchmark record carries, as JAX reports
    them — a number never travels without the device it was taken on."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def compile_cache_dir():
    """Place JAX's persistent compilation cache and return its directory,
    None when there is none.

    The package calls this once, on import: JAX decides at its first
    compile whether a cache is in use and never looks again, so the place
    that is before every compile — of a trainer, a server, a bench worker
    or a user's script — is the import. Call it again to read the path.

    - platform list forced to cpu (the test suite, the sandbox): nothing
      is touched. A cache of host programs shortens nothing that is
      measured, and ``<checkout>/.jax_cache`` travels with the checkout.
    - ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; no
      directory is set here.
    - otherwise ``<checkout>/.jax_cache`` — a fixed path, because a
      directory that moves never hits.

    The compile-time threshold drops to 0 so small programs (the generate
    prefill buckets) are cached too. The key takes in the programs'
    metadata: left out (JAX's default), a program that differs from a
    cached one only in its ``jax.named_scope`` names or source lines is
    answered with the cached executable, whose operations then carry the
    older names into every trace (seen on the chip, PR 26: the parent's
    ResNet step served the change, and no operation was under
    ``mx.opt.update``). An edit that moves a traced line costs one
    compile."""
    import os

    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if _platform_forced_to_cpu():
        return env or jax.config.jax_compilation_cache_dir
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if env:
        return env
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def cpu(device_id=0):
    """Return a CPU context (ref: python/mxnet/context.py cpu())."""
    return Context("cpu", device_id)


def cpu_pinned(device_id=0):
    return Context("cpu_pinned", device_id)


def gpu(device_id=0):
    """Accelerator context; on this stack an alias resolving to TPU."""
    return Context("gpu", device_id)


def tpu(device_id=0):
    """Return a TPU context — the native device type of this framework."""
    return Context("tpu", device_id)


def num_gpus():
    return len(_backend_devices("gpu"))


def num_tpus():
    return len(_backend_devices("tpu"))


def current_context():
    return Context.default_ctx()
