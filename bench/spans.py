"""The store's own spans in a profiler trace, line by line.

The program opens a ``jax.profiler.TraceAnnotation`` at each layer
boundary (``repro.tracing.SPANS``: ``serve.*``, ``db.*``, ``table.*``,
``wal.*``, ``lookup.*``, ``bloom.device``) and around its background work
(``bg.snapshot`` on the snapshot thread, ``bg.flush_cell`` on the flusher
pool).  Each thread is its own host line in the trace.  ``bench/trace.py``
keeps only the benchmark's spans and merges every line; this module keeps
the line, so that:

- self time (a span's duration less what its children on the same line
  cover) splits the serving thread's time by layer;
- idle device time goes to the innermost span open on the serving line
  (the line that holds ``bench.window``), never to another thread's span;
- a serving step's time during which a ``bg.*`` span is open on another
  line measures the overlap with the background threads;
- the slowest steps are named by their spans with the most self time and
  by the background spans open during them.

Events are ``[name, start_ns, end_ns, line]``; an event without a line (as
in the traces ``bench/tests/data`` recorded) belongs to one shared line.

Run as a script, it runs one traced run of a cell through the harness and
writes this split beside the result line:

    python3 bench/spans.py --workload <cell> --seed <n> --seconds <s> \
        [--out <file>]

on a machine with a TPU, from the root of a checkout; the split goes to
``--out`` (``spans-<cell>-<seed>.json`` by default) as JSON, a summary
to standard output.
The run also opens a ``gc.gen2`` span around each full garbage collection,
on the line of the thread that triggered it.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import trace  # noqa: E402

STEP = "serve.step"
BACKGROUND = "bg."
GC = "gc.gen2"          # a full collection, on the thread that triggered it
# the benchmark's own spans around the server, the engine and the forms
BENCH_SERVING = ("serving.", "engine.", "form.")


def load(path: str, names) -> list:
    """Host events of ``path`` whose names are in ``names``, each with its
    line as "<plane>#<index>"."""
    from jax.profiler import ProfileData
    names = set(names)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            out += [[e.name, e.start_ns, e.start_ns + e.duration_ns,
                     f"{plane.name}#{i}"]
                    for e in line.events if e.name in names]
    return out


def line_of(event):
    return event[3] if len(event) > 3 else None


def _lines(events) -> dict:
    by: dict = {}
    for ev in events:
        by.setdefault(line_of(ev), []).append(ev)
    return by


def window(events) -> tuple:
    """(start, end, line) of the ``bench.window`` span."""
    for ev in events:
        if ev[0] == trace.WINDOW_SPAN:
            return ev[1], ev[2], line_of(ev)
    raise ValueError(f"the trace holds no {trace.WINDOW_SPAN!r} span")


def clip(events, t0, t1) -> list:
    return [[ev[0], max(ev[1], t0), min(ev[2], t1), line_of(ev)]
            for ev in events if ev[2] > t0 and ev[1] < t1]


def self_times(events) -> dict:
    """{name: {"count", "seconds", "self_s"}}: each span's count, total
    duration and self time, its children taken on its own line only."""
    out: dict = {}
    for evs in _lines(events).values():
        stack: list = []                      # [end, name] of open spans
        for name, s, e, *_ in sorted(evs, key=lambda x: (x[1], -x[2])):
            while stack and stack[-1][0] <= s:
                stack.pop()
            d = (e - s) * 1e-9
            m = out.setdefault(name, {"count": 0, "seconds": 0.0,
                                      "self_s": 0.0})
            m["count"] += 1
            m["seconds"] += d
            m["self_s"] += d
            if stack:
                parent = out[stack[-1][1]]
                parent["self_s"] -= (min(e, stack[-1][0]) - s) * 1e-9
            stack.append([e, name])
    return out


def gaps(device: dict, t0: int, t1: int) -> list:
    """The window's device-idle intervals, as ``trace.reduce`` finds them
    (the union of every plane's operations, or of its programs)."""
    busy = []
    for lines in device.values():
        ops = [(max(s, t0), min(e, t1)) for _, s, e in lines["ops"]
               if e > t0 and s < t1]
        mods = [(max(s, t0), min(e, t1)) for _, s, e in lines["modules"]
                if e > t0 and s < t1]
        busy += trace.union(ops or mods)
    out, cur = [], t0
    for s, e in trace.union(busy):
        if s > cur:
            out.append([cur, s])
        cur = max(cur, e)
    if cur < t1:
        out.append([cur, t1])
    return out


def idle_by_span(idle, events, line) -> dict:
    """Seconds of the ``idle`` intervals by the innermost span open on
    ``line`` (``trace.OUTSIDE`` where none is)."""
    return trace.idle_by_span(
        idle, [ev[:3] for ev in events
               if line_of(ev) == line and ev[0] != trace.WINDOW_SPAN])


def _overlap(a, b) -> float:
    """Seconds in both of two sorted, merged interval lists."""
    i = j = 0
    out = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out * 1e-9


def background_overlap_s(events) -> tuple[float, float]:
    """(``serve.step`` seconds, of which seconds with a ``bg.*`` span open
    on another line)."""
    steps = [ev for ev in events if ev[0] == STEP]
    lines = {line_of(ev) for ev in steps}
    bg = trace.union((ev[1], ev[2]) for ev in events
                     if ev[0].startswith(BACKGROUND)
                     and line_of(ev) not in lines)
    busy = trace.union((ev[1], ev[2]) for ev in steps)
    return (sum(e - s for s, e in busy) * 1e-9, _overlap(busy, bg))


def slow_steps(events, program, t0: int = 0, n: int = 5,
               top: int = 2) -> list:
    """The ``n`` longest ``serve.step`` spans: seconds from ``t0`` to its
    start, its seconds, the ``top`` spans of ``program`` with the most self
    time inside it, and the ``bg.*`` spans open on other lines during it
    (seconds of overlap by name)."""
    steps = sorted((ev for ev in events if ev[0] == STEP),
                   key=lambda ev: ev[1] - ev[2])[:n]
    out = []
    for step in steps:
        s, e, line = step[1], step[2], line_of(step)
        own = self_times([ev for ev in events if line_of(ev) == line
                          and ev[1] >= s and ev[2] <= e
                          and ev[0] in program])
        bg: dict = {}
        for ev in events:
            if (ev[0].startswith(BACKGROUND) and line_of(ev) != line
                    and ev[2] > s and ev[1] < e):
                bg[ev[0]] = bg.get(ev[0], 0.0) + (
                    min(ev[2], e) - max(ev[1], s)) * 1e-9
        out.append({"at_s": (s - t0) * 1e-9, "seconds": (e - s) * 1e-9,
                    "top": sorted(([k, v["self_s"]] for k, v in own.items()),
                                  key=lambda x: -x[1])[:top],
                    "background": bg})
    return out


def reduce(events: dict, program) -> dict:
    """The split of the traced window: ``events`` as ``trace.load`` gives
    them, with host events that carry their line; ``program`` the names
    of the program's spans.  ``spans`` holds the serving line's spans,
    ``other_lines`` those of every other thread."""
    host = events["host"]
    t0, t1, line = window(host)
    inside = clip(host, t0, t1)
    step_s, bg_s = background_overlap_s(inside)
    idle = idle_by_span(gaps(events["device"], t0, t1), inside, line)
    return {"window_s": (t1 - t0) * 1e-9,
            "spans": self_times([ev for ev in inside if line_of(ev) == line
                                 and ev[0] != trace.WINDOW_SPAN]),
            "other_lines": self_times([ev for ev in inside
                                       if line_of(ev) != line]),
            "idle_by_span": sorted(([k, v] for k, v in idle.items()),
                                   key=lambda x: -x[1]),
            "steps": sum(ev[0] == STEP for ev in inside),
            "step_s": step_s, "background_overlap_s": bg_s,
            "slow_steps": slow_steps(inside, set(program), t0)}


def layer_numbers(red: dict, ctx: dict) -> dict:
    """Per-layer numbers from the split and the harness's
    ``ctx``: each layer's self ms per step, WAL read ms per get, the device
    forms' host ms per step (their spans less their programs' device
    time), the background overlap, and the share of idle time whose
    innermost span is one of the benchmark's own."""
    sp, steps = red["spans"], red["steps"]
    if not steps:
        return {}

    def total(prefixes, key="seconds"):
        return sum(v[key] for k, v in sp.items() if k.startswith(prefixes))
    out = {f"{layer}.self_ms_per_step": 1e3 * total(p, "self_s") / steps
           for layer, p in (("serving", "serve."), ("engine", "db."),
                            ("large_table", "table."))}
    if ctx["done"].get("get"):
        out["wal.read_ms_per_get"] = 1e3 * total(
            ("wal.value_read", "wal.index_pread")) / ctx["done"]["get"]
    mods = ctx["trace"]["modules"]
    device_s = sum(mods.get(p, {}).get("seconds", 0.0)
                   for p in ("jit_optimistic_lookup",
                             "jit_bloom_check_ragged"))
    out["device_forms.host_ms_per_step"] = 1e3 * (
        total(("lookup.device", "bloom.device")) - device_s) / steps
    if red["step_s"]:
        out["background.overlap_pct"] = (100 * red["background_overlap_s"]
                                         / red["step_s"])
    idle = sum(v for _, v in red["idle_by_span"])
    if idle:
        out["idle_under_bench_span_pct"] = 100 * sum(
            v for k, v in red["idle_by_span"]
            if k.startswith(BENCH_SERVING)) / idle
    return out


def summary(red: dict, log) -> None:
    """The split as log lines: spans by self time, idle by span on the
    serving line, the background overlap, the slowest steps."""
    log(f"spans: window {red['window_s']:.6f} s, {red['steps']} steps")
    for where in ("spans", "other_lines"):
        for name, v in sorted(red[where].items(),
                              key=lambda kv: -kv[1]["self_s"]):
            log(f"spans: {where} {name} n={v['count']} "
                f"total={v['seconds']:.6f} s self={v['self_s']:.6f} s")
    log("spans: idle by innermost span on the serving line: " + ", ".join(
        f"{k} {v:.6f} s" for k, v in red["idle_by_span"]))
    log(f"spans: serve.step {red['step_s']:.6f} s, of which with a bg.* "
        f"span open on another line {red['background_overlap_s']:.6f} s")
    for st in red["slow_steps"]:
        log(f"spans: slow step {st['seconds']:.6f} s: " + ", ".join(
            f"{k} {v:.6f} s" for k, v in st["top"]) + "; background: "
            + (", ".join(f"{k} {v:.6f} s" for k, v in st["background"].items())
               or "none"))
    for k, v in red.get("numbers", {}).items():
        log(f"spans: {k} = {v}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    from bench import harness
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    from repro.tracing import SPANS

    import jax
    if jax.default_backend() != "tpu":
        print("spans: needs a TPU", file=sys.stderr)
        return 2

    def log(msg, err=False):
        print(msg, file=sys.stderr if err else sys.stdout, flush=True)

    # The harness keeps only its own spans and removes the trace before
    # its readers run: its loader is wrapped to read the program's spans
    # from the same file, and each reader's ``read`` to keep the counters.
    seen: dict = {}
    load_trace, load_reader = harness.trace_mod.load, harness.load_reader

    program = set(SPANS) | {GC}

    def load_both(path, host_spans):
        out = load_trace(path, host_spans)
        seen["events"] = {"device": out["device"],
                          "host": load(path, set(host_spans) | program)}
        return out

    # Full garbage collections pause whichever thread triggered them; a
    # span around each puts them on that thread's line beside the
    # program's own spans.
    from jax.profiler import TraceAnnotation
    open_gc: list = []

    def on_gc(phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            open_gc.append(TraceAnnotation(GC))
            open_gc[-1].__enter__()
        elif open_gc:
            open_gc.pop().__exit__(None, None, None)

    def keep_ctx(name, root=harness.ROOT):
        mod = load_reader(name, root)
        read = mod.read
        mod.read = lambda ctx: (seen.setdefault("ctx", ctx), read(ctx))[1]
        return mod

    cell = harness.load_cell(args.workload)
    harness.trace_mod.load, harness.load_reader = load_both, keep_ctx
    gc.callbacks.append(on_gc)
    try:
        result = harness.run_cell(cell, args.seed, args.seconds, True,
                                  t_process=T_PROCESS, log=log)
    finally:
        gc.callbacks.remove(on_gc)
        harness.trace_mod.load, harness.load_reader = load_trace, load_reader
    red = reduce(seen["events"], program)
    ctx = seen["ctx"]
    red["numbers"] = layer_numbers(red, ctx)
    red["counters"] = {"db": ctx["db"], "srv": ctx["srv"],
                       "done": ctx["done"]}
    red["result"] = result
    path = args.out or f"spans-{cell.name}-{args.seed}.json"
    with open(path, "w") as f:
        json.dump(red, f, indent=1)
    summary(red, log)
    log(f"spans: written to {path}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
