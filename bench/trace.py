"""From a profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
three things, all in nanoseconds on the trace's one clock:

- per TPU plane, the events of its "XLA Ops" line (each operation the
  device ran) and of its "XLA Modules" line (each jitted program run);
- the host spans the benchmark itself records (``jax.profiler.
  TraceAnnotation``), by name.

``reduce`` turns that into what the metrics read: the traced window (the
``bench.window`` span), the device's busy time (the union of operation
intervals, averaged over the planes), the device time and run count of
each jitted program by name, the operations that took most time, and the
idle time attributed to the innermost benchmark span the host was in.
The reduction is plain Python over lists, so it is tested on a small
recorded trace kept in ``bench/tests/data``.
"""
from __future__ import annotations

import re

WINDOW_SPAN = "bench.window"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_RUN_SUFFIX = re.compile(r"\(\d+\)$")
OUTSIDE = "(no bench span)"


def load(path: str, host_spans) -> dict:
    """{"device": {plane: {"ops": [...], "modules": [...]}}, "host": [...]},
    every event a [name, start_ns, end_ns] list."""
    from jax.profiler import ProfileData
    host_spans = set(host_spans)
    out = {"device": {}, "host": []}
    for plane in ProfileData.from_file(path).planes:
        if _DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            out["device"][plane.name] = {
                key: [[e.name, e.start_ns, e.start_ns + e.duration_ns]
                      for e in lines[name].events] if name in lines else []
                for key, name in (("ops", OPS_LINE),
                                  ("modules", MODULES_LINE))}
        elif plane.name.startswith("/host:"):
            out["host"] += [[e.name, e.start_ns, e.start_ns + e.duration_ns]
                            for line in plane.lines for e in line.events
                            if e.name in host_spans]
    return out


def union(intervals) -> list:
    """Sorted, merged [start, end] intervals."""
    merged: list = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(events, t0, t1) -> list:
    return [[n, max(s, t0), min(e, t1)] for n, s, e in events
            if e > t0 and s < t1]


def module_name(name: str) -> str:
    """A program's trace name without the run counter some traces append."""
    return _RUN_SUFFIX.sub("", name).strip()


def op_name(name: str) -> str:
    """An operation's HLO instruction name: TPU traces name each op by its
    whole HLO text, "%fusion.3 = s32[...] fusion(...), kind=..."."""
    return name.split(" = ", 1)[0].lstrip("%")


def _op_names(ops, modules) -> list:
    """Each op as "<module>/<op>": the program run whose interval holds it."""
    mods = sorted((s, e, module_name(n)) for n, s, e in modules)
    out, j = [], 0
    for n, s, e in sorted(ops, key=lambda x: x[1]):
        while j < len(mods) and mods[j][1] < s:
            j += 1
        owner = mods[j][2] if j < len(mods) and mods[j][0] <= s else "?"
        out.append([f"{owner}/{op_name(n)}", s, e])
    return out


def idle_by_span(gaps, spans) -> dict:
    """Seconds of each gap, split by the innermost span open at each
    instant (the one that opened last); time under no span counts as
    OUTSIDE.  Spans of one thread nest, so the last opened is innermost."""
    cuts = sorted({t for _, s, e in spans for t in (s, e)}
                  | {t for g in gaps for t in g})
    starts: dict = {}
    ends: dict = {}
    for i, (name, s, e) in enumerate(spans):
        starts.setdefault(s, []).append(i)
        ends.setdefault(e, []).append(i)
    active: dict = {}
    out: dict = {}
    gi = 0
    for a, b in zip(cuts, cuts[1:]):
        for i in ends.get(a, ()):
            active.pop(i, None)
        for i in starts.get(a, ()):
            active[i] = spans[i][1]
        while gi < len(gaps) and gaps[gi][1] <= a:
            gi += 1
        if gi < len(gaps) and gaps[gi][0] <= a and b <= gaps[gi][1]:
            name = (spans[max(active, key=lambda i: (active[i], -spans[i][2]))][0]
                    if active else OUTSIDE)
            out[name] = out.get(name, 0.0) + (b - a) * 1e-9
    return out


def reduce(trace: dict, top: int = 10) -> dict:
    """The traced window's device numbers; seconds throughout."""
    windows = [(s, e) for n, s, e in trace["host"] if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    t0, t1 = windows[0]
    planes = trace["device"]
    if not planes:
        raise ValueError("the trace holds no TPU plane")
    busy_sets, modules, ops = [], {}, {}
    for lines in planes.values():
        plane_ops = _clip(lines["ops"], t0, t1)
        plane_mods = _clip(lines["modules"], t0, t1)
        busy_sets.append(union((s, e) for _, s, e in plane_ops or plane_mods))
        for n, s, e in plane_mods:
            m = modules.setdefault(module_name(n), {"seconds": 0.0, "runs": 0})
            m["seconds"] += (e - s) * 1e-9 / len(planes)
            m["runs"] += 1
        for n, s, e in _op_names(plane_ops, plane_mods):
            ops[n] = ops.get(n, 0.0) + (e - s) * 1e-9 / len(planes)
    busy_s = sum(sum(e - s for s, e in b) for b in busy_sets) * 1e-9 / len(planes)
    busy_any = union(iv for b in busy_sets for iv in b)
    gaps, cur = [], t0
    for s, e in busy_any:
        if s > cur:
            gaps.append([cur, s])
        cur = max(cur, e)
    if cur < t1:
        gaps.append([cur, t1])
    spans = [x for x in _clip(trace["host"], t0, t1) if x[0] != WINDOW_SPAN]
    idle = idle_by_span(gaps, spans)
    return {
        "window_s": (t1 - t0) * 1e-9,
        "busy_s": busy_s,
        "modules": modules,
        "device_ops": sorted(([n, s] for n, s in ops.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": sorted(([n, s] for n, s in idle.items()),
                            key=lambda x: -x[1])[:top],
    }
