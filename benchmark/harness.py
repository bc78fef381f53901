"""What every cell's run shares: finding the cell's files by the names in
``BENCHMARK.json``, the device check, compile counting, the traced segment,
the per-layer readers and the result line.

A cell is data.  ``BENCHMARK.json`` names the cell, its configuration and its
traffic mix; from those names alone the harness finds

- ``<configs[].file>``                         the configuration as it is run,
- ``benchmark/traffic/<traffic>.json``         the mix's parameters; its
  ``runner`` names ``benchmark/runners/<runner>.py``,
- ``benchmark/workloads/<cell>.json``          the limits ``correct`` holds,
- ``benchmark/metrics/<metric>.json``          a per-layer metric: its
  ``reader`` names ``benchmark/readers/<reader>.py``, ``args`` are its own.

A later PR adds files and ``BENCHMARK.json`` entries and edits nothing here.
"""
import importlib.util
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT_DIR = os.path.join(ROOT, ".bench_out")       # git-ignored scratch


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(kind, name):
    """``benchmark/<kind>/<name>.py`` as a module, found by name."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit("benchmark: no %s named %r (%s)" % (kind, name, path))
    spec = importlib.util.spec_from_file_location("benchmark_%s_%s" % (kind, name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with everything its names lead to."""

    def __init__(self, bench_path, workload):
        self.bench = load_json(bench_path)
        rows = [w for w in self.bench["workloads"] if w["name"] == workload]
        if not rows:
            raise SystemExit("benchmark: no workload %r in %s" % (workload, bench_path))
        self.entry = rows[0]
        self.name = workload
        self.chips = int(self.entry["chips"])
        cfg = [c for c in self.bench["configs"] if c["name"] == self.entry["config"]][0]
        self.config = load_json(os.path.join(ROOT, cfg["file"]))
        # traffic and limits sit beside the configs directory, so a rehearsal
        # keeps its own tiny files beside its own BENCHMARK.json
        self.dir = os.path.dirname(os.path.dirname(os.path.join(ROOT, cfg["file"])))
        self.traffic = load_json(os.path.join(self.dir, "traffic",
                                              self.entry["traffic"] + ".json"))
        self.limits = load_json(os.path.join(self.dir, "workloads",
                                             workload + ".json"))["limits"]

    def _listed(self, group):
        return [m for m in self.bench[group]
                if self.name in m.get("workloads", [self.name])]

    def end_to_end(self):
        return self._listed("end_to_end")

    def per_layer(self):
        return self._listed("per_layer")


class CompileCounter:
    """Backend compilations, by JAX's own monitoring events: the programs
    that were not answered by the persistent cache or the jit cache."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def from_scratch(self):
        # a persistent-cache hit still raises the duration event
        return self.compiles - self.cache_hits

    def mark_window(self):
        """What set-up compiled; the rest is the window's."""
        self.at_window = self.from_scratch()


def require_devices(chips, rehearsal):
    """The devices the cell runs on.  No TPU, or fewer chips than the cell
    asks for: exit code 2 and no result.  Only a rehearsal takes the CPU."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not rehearsal:
        print("benchmark: no TPU (platform %r); nothing was measured"
              % platform, file=sys.stderr)
        raise SystemExit(2)
    if len(devices) < chips:
        print("benchmark: the cell needs %d chip(s), JAX sees %d"
              % (chips, len(devices)), file=sys.stderr)
        raise SystemExit(2)
    return devices[:chips]


def peaks_for(device):
    """Peak rates of this device kind; a kind that is not in the table is an
    error, never a default."""
    table = load_json(os.path.join(HERE, "peaks.json"))["devices"]
    if device.device_kind not in table:
        raise SystemExit("benchmark: no peaks for device kind %r in peaks.json"
                         % device.device_kind)
    return table[device.device_kind]


def memory_peak_bytes(devices):
    """Peak bytes held on the fullest chip, read when the window has closed.
    The TPU's allocator counts the arrays a process holds (``bytes_in_use``)
    apart from what it has set aside for the compiled programs' own
    temporaries (``bytes_reserved``), and a chip holds both at once while a
    step runs; their two peaks need not fall together (set-up's transient
    copies pass before the largest program is loaded), so the reading is the
    larger of the arrays' own peak and what is held at the close."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(max(stats.get("peak_bytes_in_use", 0),
                         stats.get("bytes_in_use", 0) + stats.get("bytes_reserved", 0)))
    return int(max(peaks))


class Tracer:
    """Traces a few seconds of the window with ``jax.profiler``.

    The runner calls ``poll`` at a boundary of its work (a step, a batch)
    with the work done so far and a ``sync`` that waits for the device.  The
    traced segment starts ``after_s`` into the window and lasts ``seconds``;
    work and host clock at both ends give the segment's own rate.
    """

    def __init__(self, enabled, cell_name, after_s=2.0, seconds=3.0):
        self.enabled = bool(enabled)
        self.after_s, self.seconds = float(after_s), float(seconds)
        self.dir = os.path.join(OUT_DIR, "trace", cell_name)
        self.state = "off" if not self.enabled else "waiting"
        self.t0 = self.t1 = None
        self.work0 = self.work1 = None
        self._span = None
        self.on_window = None

    def begin_window(self, now):
        """Every runner calls this where its window starts."""
        self.window_start = now
        if self.on_window:
            self.on_window()

    def poll(self, work, sync):
        if self.state in ("off", "done"):
            return
        import jax

        now = time.perf_counter()
        if self.state == "waiting" and now - self.window_start >= self.after_s:
            sync()
            shutil.rmtree(self.dir, ignore_errors=True)
            jax.profiler.start_trace(self.dir)
            self._span = jax.profiler.TraceAnnotation("bench_traced_segment")
            self._span.__enter__()
            self.t0, self.work0 = time.perf_counter(), dict(work)
            self.state = "tracing"
        elif self.state == "tracing" and now - self.t0 >= self.seconds:
            self.finish(work, sync)

    def finish(self, work, sync):
        if self.state != "tracing":
            if self.state == "waiting":
                self.state = "done"
            return
        import jax

        sync()
        self.t1, self.work1 = time.perf_counter(), dict(work)
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"

    def segment(self):
        """Work done and host seconds of the traced segment, or None."""
        if self.t1 is None:
            return None
        work = {k: self.work1[k] - self.work0.get(k, 0) for k in self.work1}
        return {"seconds": self.t1 - self.t0, "work": work}

    def trace_file(self):
        for dirpath, _dirs, files in os.walk(self.dir):
            for f in files:
                if f.endswith(".xplane.pb"):
                    return os.path.join(dirpath, f)
        return None

    def cleanup(self):
        shutil.rmtree(self.dir, ignore_errors=True)


def span(name):
    """A host span in the profiler's trace, around a call into a layer."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def read_per_layer(cell, context):
    """Every per-layer metric the cell lists, through its own reader.  A
    reader that finds nothing to read returns None and the metric is left
    out of the line."""
    out = {}
    for m in cell.per_layer():
        spec = load_json(os.path.join(HERE, "metrics", m["name"] + ".json"))
        reader = load_module("readers", spec["reader"])
        value = reader.read(context, spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def judge(checks):
    """``correct`` from the numbers compared: each at or under its limit, and
    a number that is not finite fails."""
    ok = True
    for c in checks:
        v = c["value"]
        c["ok"] = bool(v == v and v not in (float("inf"), float("-inf"))
                       and v <= c["limit"])
        ok = ok and c["ok"]
    return ok and bool(checks)


def print_result(result, checks):
    """Each number compared beside its limit as the last lines of standard
    error, then the result as the last line of standard output, the compared
    numbers under a key of their own that comes last."""
    sys.stdout.flush()
    for c in checks:
        print("compared %s = %.6g (limit %.6g) %s"
              % (c["name"], c["value"], c["limit"], "ok" if c["ok"] else "OVER"),
              file=sys.stderr)
    sys.stderr.flush()
    result["compared"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                          for c in checks}
    print(json.dumps(result), flush=True)
