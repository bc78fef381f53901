"""What the readers of the program's own spans share: the traced run's
``.xplane.pb`` read once a process, the ``mx.*`` host spans of the traced
segment with the thread each ran on, the device's idle intervals, and the
split of that idle time among the spans.

The program opens its spans through ``mxnet_tpu.profiler.span``, which enters
a ``jax.profiler.TraceAnnotation``: in a traced run they are events on the
host plane's lines (one line a thread) of the same file as the device's
operations, on one clock.  A program that opens none (the parent of the PR
that brought them) gives empty lists, and every reader then returns nothing.

The readers' context carries no path, so the trace is found where the run's
``harness.Tracer`` put it, by a second ``Tracer`` of the same cell.
"""
import bisect
import json
import os
import sys

from benchmark import harness, trace_reduce

PREFIX = "mx."
_CACHE = {}


def for_context(ctx):
    """The traced segment's spans and idle time (``view``), or None where the
    run has no trace or no device plane."""
    if not ctx.get("trace") or not ctx.get("segment"):
        return None
    cell = ctx["cell"]
    path = harness.Tracer(False, cell.name).trace_file()
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = dict(view(trace_reduce._load(path), cell.chips), path=path)
    return _CACHE[key]


def view(data, chips=1):
    """From a ``ProfileData``: the segment ``(lo, hi)`` in ns, the ``mx.*``
    spans as (start, end, name, thread) clipped to it together with each
    span's whole length, per chip the idle intervals, the complement of
    the union of the ``XLA Ops`` events inside the segment, and the first
    chip's compiled programs as (start, end, name).  A span that was open
    when the trace started, or still is when it stops, is not in the trace:
    what the device idles under it counts as under no span."""
    spans, segment = [], None
    thread = 0
    for p in data.planes:
        if p.name.startswith("/device:"):
            continue
        for line in p.lines:
            thread += 1
            for e in line.events:
                if e.name == trace_reduce.SEGMENT:
                    segment = (int(e.start_ns), int(e.start_ns) + int(e.duration_ns))
                elif e.name.startswith(PREFIX):
                    a = int(e.start_ns)
                    spans.append((a, a + int(e.duration_ns), e.name, thread))
    planes = trace_reduce._device_planes(data)[:chips]
    busy, modules = [], []
    for p in planes:
        lines = [ln for ln in p.lines if ln.name == trace_reduce.OPS_LINE]
        busy.append([(a, b) for a, b, _n, _e in trace_reduce._events(lines[0])]
                    if lines else [])
    for ln in (planes[0].lines if planes else []):
        if ln.name == trace_reduce.MODULES_LINE:
            modules += [(a, b, n.split("(")[0]) for a, b, n, _e in trace_reduce._events(ln)]
    if segment is None:
        every = [t for ev in busy for ab in ev for t in ab]
        if not every:
            return {"segment": None, "spans": [], "idle": [], "modules": []}
        segment = (min(every), max(every))
    lo, hi = segment
    clipped = [(max(a, lo), min(b, hi), name, thread, b - a)
               for a, b, name, thread in spans if b > lo and a < hi and b > a]
    idle = []
    for events in busy:
        merged = trace_reduce._union([(max(a, lo), min(b, hi)) for a, b in events
                                      if b > lo and a < hi])
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        idle.append([(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a])
    return {"segment": segment, "spans": clipped, "idle": idle, "modules": modules}


def covered(intervals):
    return sum(b - a for a, b in trace_reduce._union(intervals))


def span_seconds(v, name, self_time=False, less_module=None):
    """(seconds, spans) of the spans called ``name`` inside the segment: a
    span the segment cuts counts as the part of one that lies inside.  With
    ``self_time``, less what the ``mx.*`` spans nested in it (same thread,
    inside its extent, shorter) cover; with ``less_module`` (a compiled
    regular expression), less the time the compiled programs it matches ran
    on the device while the span was open."""
    spans = v["spans"]
    ns, count = 0, 0.0
    for a, b, n, thread, whole in spans:
        if n != name:
            continue
        ns += b - a
        count += (b - a) / whole
        if self_time:
            ns -= covered([(c, d) for c, d, _m, t, w in spans
                           if t == thread and c >= a and d <= b and w < whole])
        if less_module is not None:
            ns -= covered([(max(a, c), min(b, d)) for c, d, m in v["modules"]
                           if d > a and c < b and less_module.search(m)])
    return ns / 1e9, count


def idle_by_span(v):
    """{span name or None: seconds} of the device's idle time in the segment,
    averaged over the chips: each instant goes to the innermost ``mx.*`` span
    open on any thread, the one that started last, and to None where no span
    is open.  The values sum to the segment's idle time."""
    spans = v["spans"]
    cuts = sorted({t for a, b, *_ in spans for t in (a, b)} | set(v["segment"]))
    out = {}
    for idle in v["idle"]:
        starts = [a for a, _b in idle]
        total = [0]
        for a, b in idle:
            total.append(total[-1] + (b - a))

        def before(t):
            """Idle time up to ``t``."""
            i = bisect.bisect_right(starts, t)
            if i == 0:
                return 0
            a, b = idle[i - 1]
            return total[i - 1] + min(t, b) - a

        for a, b in zip(cuts, cuts[1:]):
            ns = before(b) - before(a)
            if ns <= 0:
                continue
            open_now = [(s, name) for s, e, name, _t, _w in spans if s <= a and e >= b]
            label = max(open_now)[1] if open_now else None
            out[label] = out.get(label, 0.0) + ns / 1e9 / len(v["idle"])
    return out


def hlo_stats(path):
    """xprof's ``hlo_stats`` of the whole trace, which the harness starts and
    stops with the segment: one row an HLO instruction with its ``op_name``
    metadata (``tf_op_name``, where ``jax.named_scope`` and a kernel's name
    land) and its self time.  ``jax.profiler.ProfileData`` shows the events'
    names and times but not that metadata.  [] where xprof cannot be had."""
    key = ("hlo_stats", path)
    if key not in _CACHE:
        try:
            from xprof.convert import _pywrap_profiler_plugin as plugin

            raw, _ok = plugin.xspace_to_tools_data([path], "hlo_stats", {})
            table = json.loads(raw)
            ids = [c["id"] for c in table["cols"]]
            rows = [dict(zip(ids, (c.get("v") for c in r["c"]))) for r in table["rows"]]
        except Exception as e:               # noqa: BLE001  a reader never fails a run
            print("program_spans: no hlo_stats from xprof: %r" % e, file=sys.stderr)
            rows = []
        _CACHE[key] = rows
    return _CACHE[key]
