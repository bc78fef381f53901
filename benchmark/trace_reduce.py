"""From a ``jax.profiler`` trace (``.xplane.pb``) to what the metrics read.

Read with ``jax.profiler.ProfileData`` alone.  A TPU's plane is named
``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per executed HLO
operation, nested where an operation (a ``while`` of a scanned layer stack)
contains others.  The host's plane holds one line per thread, and the
``TraceAnnotation`` spans the benchmark puts around its calls are events
there, on the same clock.

``reduce`` returns, for the traced segment (the host span
``bench_traced_segment`` where it is found, else the extent of the device's
events):

- ``window_s``   the segment's length,
- ``busy_s``     seconds in which an operation ran on the device: the union of
  the ``XLA Ops`` intervals, averaged over the chips used,
- ``ops``        {name: {"seconds": self time, "count": n}} by operation, self
  time being an event's duration less what its nested events cover, summed
  over chips and divided by their number,
- ``modules``    {name: {"seconds", "count"}} by compiled program (the
  ``XLA Modules`` line: one event per execution of a jitted function, named
  ``jit_<function>(<fingerprint>)``), clipped to the segment,
- ``top_ops``    the ten longest of those operations as [name, seconds],
- ``top_gaps``   idle time by what the host was doing, as [label, seconds]:
  each gap in the device's busy union is labelled with the innermost
  ``bench_*`` host span that covers its start (``unlabelled`` where none does).
"""
import re
import sys

SEGMENT = "bench_traced_segment"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MIN_GAP_NS = 2_000


def _load(path):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def _device_planes(data):
    return [p for p in data.planes if p.name.startswith("/device:TPU:")]


def _events(line):
    return [(int(e.start_ns), int(e.start_ns) + int(e.duration_ns), e.name, e)
            for e in line.events]


def _union(intervals):
    """Merged, sorted intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def _self_times(events):
    """(name, self_ns) for nested events of one line: an event's duration
    minus the part its children cover."""
    out = []
    stack = []                                  # [end, name, duration, child_ns]
    for a, b, name, _e in sorted(events, key=lambda t: (t[0], -t[1])):
        while stack and a >= stack[-1][0]:
            end, n, dur, child = stack.pop()
            out.append((n, dur - child))
        if stack:
            stack[-1][3] += min(b, stack[-1][0]) - a
        stack.append([b, name, b - a, 0])
    while stack:
        end, n, dur, child = stack.pop()
        out.append((n, dur - child))
    return out


def short_name(name):
    """An HLO event's own text is the whole instruction; keep its result
    name, its opcode and, for a custom call, its target."""
    head, _, rest = name.partition(" = ")
    if not rest:
        return name[:80]
    op = re.search(r"\s([a-z][a-z0-9\-]*)\(", " " + rest)
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    parts = [head.lstrip("%"), op.group(1) if op else ""]
    if target:
        parts.append(target.group(1))
    return " ".join(x for x in parts if x)[:80]


def host_spans(data, prefix="bench_"):
    spans = []
    for p in data.planes:
        if p.name.startswith("/device:"):
            continue
        for line in p.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    spans.append((int(e.start_ns), int(e.start_ns) + int(e.duration_ns),
                                  e.name))
    return spans


def reduce(path, chips=1, data=None):
    data = data if data is not None else _load(path)
    planes = _device_planes(data)[:chips]
    if not planes:
        raise RuntimeError("trace %s has no /device:TPU plane" % path)
    spans = host_spans(data)
    seg = [s for s in spans if s[2] == SEGMENT]
    per_plane = []
    for p in planes:
        lines = [ln for ln in p.lines if ln.name == OPS_LINE]
        per_plane.append(_events(lines[0]) if lines else [])
    if seg:
        lo, hi = seg[0][0], seg[0][1]
    else:
        every = [t for ev in per_plane for t in ev]
        lo, hi = min(t[0] for t in every), max(t[1] for t in every)

    busy_ns, ops, gaps, modules = 0, {}, {}, {}
    for p in planes:
        for ln in p.lines:
            if ln.name != MODULES_LINE:
                continue
            for a, b, name, _e in _events(ln):
                if b <= lo or a >= hi:
                    continue
                rec = modules.setdefault(name.split("(")[0], {"seconds": 0.0, "count": 0})
                rec["seconds"] += (min(b, hi) - max(a, lo)) / 1e9 / len(planes)
                rec["count"] += 1
    inner = sorted((s for s in spans if s[2] != SEGMENT), key=lambda s: s[1] - s[0])
    for events in per_plane:
        clipped = [(max(a, lo), min(b, hi), n, e) for a, b, n, e in events
                   if b > lo and a < hi]
        merged = _union([(a, b) for a, b, _n, _e in clipped])
        busy_ns += sum(b - a for a, b in merged)
        for name, ns in _self_times(clipped):
            rec = ops.setdefault(name, {"seconds": 0.0, "count": 0})
            rec["seconds"] += ns / 1e9 / len(planes)
            rec["count"] += 1
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b - a < MIN_GAP_NS:
                continue
            label = "unlabelled"
            for s in inner:                      # shortest covering span first
                if s[0] <= a < s[1]:
                    label = s[2]
                    break
            gaps[label] = gaps.get(label, 0.0) + (b - a) / 1e9 / len(planes)
    top_ops = sorted(([short_name(n), r["seconds"]] for n, r in ops.items()),
                     key=lambda t: -t[1])[:10]
    top_gaps = sorted(([n, s] for n, s in gaps.items()), key=lambda t: -t[1])[:10]
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy_ns / 1e9 / len(planes),
            "ops": ops, "modules": modules, "top_ops": top_ops, "top_gaps": top_gaps,
            "segment_found": bool(seg)}


def matching(table, pattern):
    """(seconds, count) summed over the entries of ``ops`` or ``modules``
    whose names match ``pattern``."""
    rx = re.compile(pattern)
    hits = [r for n, r in table.items() if rx.search(n)]
    return sum(r["seconds"] for r in hits), sum(r["count"] for r in hits)


def dump(path, limit=40):
    """What a trace holds, for a look by hand: planes, lines, and the longest
    device operations with their stats."""
    data = _load(path)
    for p in data.planes:
        print("plane %r" % p.name)
        for line in p.lines:
            ev = list(line.events)
            print("   line %r: %d events" % (line.name, len(ev)))
    for p in _device_planes(data)[:1]:
        for line in p.lines:
            if line.name != OPS_LINE:
                continue
            seen = {}
            for e in line.events:
                rec = seen.setdefault(e.name, [0, 0, e])
                rec[0] += int(e.duration_ns)
                rec[1] += 1
            for name, (ns, n, e) in sorted(seen.items(), key=lambda t: -t[1][0])[:limit]:
                stats = {k: (str(v)[:160]) for k, v in e.stats}
                print("%10.3f ms x%-5d %s  %s" % (ns / 1e6, n, name, stats))
    r = reduce(path, data=data)
    print({k: r[k] for k in ("window_s", "busy_s", "modules", "top_ops", "top_gaps",
                             "segment_found")})


if __name__ == "__main__":
    dump(sys.argv[1])
