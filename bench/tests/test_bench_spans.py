"""The program's spans line by line (bench/spans.py): self time, idle time
over the serving line only, the overlap with the background threads, on
a hand-made two-line trace, on the recorded v5e traces (one line) and on
a CPU trace of the store itself; and the readers of the store's device
and serving counters."""
import hashlib
import json
import os
import shutil
import sys
import tempfile
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness, spans, trace  # noqa: E402

US = 1000                                  # events are in nanoseconds
TPU = "/device:TPU:0"
MAIN, SNAP, POOL = "/host:CPU#0", "/host:CPU#1", "/host:CPU#2"


def _ev(name, s, e, line=MAIN):
    return [name, s * US, e * US, line]


# The serving thread's spans nest; the snapshot thread opens bg.snapshot
# at 400 us, after lookup.device (300 us) and inside it: merged into one
# line, the idle time from 400 to 500 us would go to bg.snapshot.
TWO_LINES = {
    "host": [_ev("bench.window", 0, 1000), _ev("bench.topup", 0, 100),
             _ev("serving.step", 100, 1000), _ev("serve.step", 110, 990),
             _ev("serve.schedule", 110, 150), _ev("serve.reads", 150, 900),
             _ev("db.multi_get", 160, 880), _ev("table.resolve", 200, 620),
             _ev("lookup.device", 300, 500),
             _ev("wal.value_read", 650, 840),
             _ev("serve.writes", 900, 980), _ev("db.put_many", 905, 975),
             _ev("bg.snapshot", 20, 60, SNAP),
             _ev("bg.snapshot", 400, 700, SNAP),
             _ev("bg.flush_cell", 450, 650, POOL)],
    "device": {TPU: {"modules": [["jit_optimistic_lookup(1)", 320 * US,
                                  480 * US]],
                     "ops": [["custom-call", 320 * US, 480 * US]]}},
}
SELF_US = {"bench.topup": 100, "serving.step": 20, "serve.step": 10,
           "serve.schedule": 40, "serve.reads": 30, "db.multi_get": 110,
           "table.resolve": 220, "lookup.device": 200,
           "wal.value_read": 190, "serve.writes": 10, "db.put_many": 70}
IDLE_US = {"bench.topup": 100, "serving.step": 20, "serve.step": 10,
           "serve.schedule": 40, "serve.reads": 30, "db.multi_get": 110,
           "table.resolve": 220, "lookup.device": 40,
           "wal.value_read": 190, "serve.writes": 10, "db.put_many": 70}


def _us(d):
    return pytest.approx({k: v * 1e-6 for k, v in d.items()})


def test_self_time_idle_and_overlap_on_two_lines():
    red = spans.reduce(TWO_LINES, ["serve.step", "serve.schedule",
                                   "serve.reads", "serve.writes",
                                   "db.multi_get", "db.put_many",
                                   "table.resolve", "lookup.device",
                                   "wal.value_read"])
    assert red["window_s"] == pytest.approx(1000e-6)
    assert {k: v["self_s"] for k, v in red["spans"].items()} == _us(SELF_US)
    assert {k: v["self_s"] for k, v in red["other_lines"].items()} == \
        _us({"bg.snapshot": 340, "bg.flush_cell": 200})
    assert red["other_lines"]["bg.snapshot"]["count"] == 2
    assert red["spans"]["db.multi_get"]["seconds"] == pytest.approx(720e-6)
    # idle only over the line that holds bench.window, by its innermost span
    assert dict(red["idle_by_span"]) == _us(IDLE_US)
    assert red["steps"] == 1
    assert red["step_s"] == pytest.approx(880e-6)
    assert red["background_overlap_s"] == pytest.approx(300e-6)
    [slow] = red["slow_steps"]
    assert slow["at_s"] == pytest.approx(110e-6)
    assert slow["seconds"] == pytest.approx(880e-6)
    assert [k for k, _ in slow["top"]] == ["table.resolve", "lookup.device"]
    assert slow["background"] == _us({"bg.snapshot": 300,
                                      "bg.flush_cell": 200})


def test_merged_lines_would_give_idle_time_to_another_thread():
    t0, t1, _ = spans.window(TWO_LINES["host"])
    gaps = spans.gaps(TWO_LINES["device"], t0, t1)
    merged = trace.idle_by_span(gaps, [ev[:3] for ev in TWO_LINES["host"]
                                       if ev[0] != "bench.window"])
    assert merged["bg.snapshot"] > 0
    per_line = spans.idle_by_span(gaps, TWO_LINES["host"], MAIN)
    assert "bg.snapshot" not in per_line
    assert sum(per_line.values()) == pytest.approx(sum(merged.values()))


def test_layer_numbers_of_the_split():
    red = spans.reduce(TWO_LINES, set(SELF_US))
    ctx = {"done": {"get": 10, "put": 0},
           "trace": {"modules": {"jit_optimistic_lookup":
                                 {"seconds": 160e-6, "runs": 1}}}}
    got = spans.layer_numbers(red, ctx)
    assert got == pytest.approx({
        "serving.self_ms_per_step": 0.090, "engine.self_ms_per_step": 0.180,
        "large_table.self_ms_per_step": 0.220,
        "wal.read_ms_per_get": 0.019,
        "device_forms.host_ms_per_step": 0.040,
        "background.overlap_pct": 100 * 300 / 880,
        "idle_under_bench_span_pct": 100 * 20 / 840})


RECORDED = sorted((os.path.join(ROOT, "bench", "tests", "data", f)
                   for f in os.listdir(os.path.join(ROOT, "bench", "tests",
                                                    "data"))
                   if f.startswith("trace_")))


@pytest.mark.parametrize("path", RECORDED,
                         ids=[os.path.basename(p) for p in RECORDED])
def test_recorded_traces_reduce_as_before_and_as_one_line(path):
    """The recorded v5e traces carry no line per event: they are one line,
    and the per-line split gives the idle time exactly as ``trace.reduce``
    does."""
    with open(path) as f:
        events = json.load(f)["events"]
    idle = dict(trace.reduce(events)["idle_gaps"])
    t0, t1, line = spans.window(events["host"])
    assert line is None
    mine = spans.idle_by_span(spans.gaps(events["device"], t0, t1),
                              spans.clip(events["host"], t0, t1), line)
    assert mine == pytest.approx(idle, rel=1e-9)
    assert spans.reduce(events, ())["background_overlap_s"] == 0


# --------------------------------------------------- the store, traced
def _keys(n, tag):
    return [hashlib.sha256(f"{tag}{i}".encode()).digest() for i in range(n)]


PARENT = {
    "serve.schedule": {"serve.step"}, "serve.reads": {"serve.step"},
    "serve.writes": {"serve.step"}, "db.multi_get": {"serve.reads"},
    "db.multi_exists": {"serve.reads"}, "db.put_many": {"serve.writes"},
    "db.cache_sweep": {"db.multi_get", "db.multi_exists"},
    "db.cache_fill": {"db.multi_get"},
    "table.resolve": {"db.multi_get", "db.multi_exists"},
    "table.bloom_pass": {"table.resolve"},
    "bloom.device": {"table.bloom_pass"},
    "table.blob_load": {"table.resolve"},
    "wal.index_pread": {"table.blob_load"},
    "lookup.device": {"table.resolve"}, "table.verify": {"table.resolve"},
    "lookup.host_search": {"table.resolve"}, "table.perkey": {"table.resolve"},
    "wal.value_read": {"db.multi_get"},
    "wal.append_many": {"db.put_many"}, "table.apply_many": {"db.put_many"},
}


def _parents(events) -> dict:
    """{(name, start): parent name or None} on each event's own line."""
    out = {}
    by_line: dict = {}
    for ev in events:
        by_line.setdefault(spans.line_of(ev), []).append(ev)
    for evs in by_line.values():
        stack: list = []
        for name, s, e, _ in sorted(evs, key=lambda x: (x[1], -x[2])):
            while stack and stack[-1][1] <= s:
                stack.pop()
            out[(name, s)] = stack[-1][0] if stack else None
            stack.append((name, e))
    return out


def test_a_traced_step_nests_by_layer_and_background_has_its_own_lines():
    import jax
    from repro.core.tidestore import DbConfig, KeyspaceConfig, TideDB
    from repro.core.tidestore.wal import WalConfig
    from repro.serving.engine import KvBatchServer
    from repro.tracing import SPANS

    d = tempfile.mkdtemp(prefix="tide-spans-")
    cfg = DbConfig(keyspaces=[KeyspaceConfig("default", n_cells=8,
                                             dirty_flush_threshold=1)],
                   wal=WalConfig(segment_size=1 << 20, background=False),
                   index_wal=WalConfig(segment_size=1 << 20,
                                       background=False),
                   snapshot_interval_s=0.02, cache_bytes=1 << 20,
                   blob_cache_bytes=0)
    present = _keys(2048, "p")
    db = TideDB(d, cfg)
    try:
        db.put_many([(k, b"v" * 16) for k in present])
        db.snapshot_now(flush_threshold=1)       # every cell on disk
        srv = KvBatchServer(db, max_batch=4096)
        tdir = os.path.join(d, "trace")
        jax.profiler.start_trace(tdir)
        try:
            for k in present[:512]:
                srv.submit_get(k)
            for k in present[512:1024] + _keys(512, "a"):
                srv.submit_exists(k)
            for k in _keys(64, "w"):
                srv.submit_put(k, b"w" * 16)
            flushes = db.stats()["index_flushes"]
            assert srv.step() == 512 + 1024 + 64
            deadline = time.monotonic() + 10
            while (db.stats()["index_flushes"] == flushes
                   and time.monotonic() < deadline):
                time.sleep(0.02)
            time.sleep(0.05)
        finally:
            jax.profiler.stop_trace()
        [path] = [os.path.join(r, f) for r, _, fs in os.walk(tdir)
                  for f in fs if f.endswith(".xplane.pb")]
        events = spans.load(path, SPANS)
    finally:
        db.close()
        shutil.rmtree(d, ignore_errors=True)
    names = {ev[0] for ev in events}
    # the kernel resolves every query here, and each cell takes its blob
    assert set(PARENT) - {"lookup.host_search", "table.perkey"} | {
        "serve.step", "bg.snapshot", "bg.flush_cell"} <= names
    parents = _parents(events)
    [step] = [ev for ev in events if ev[0] == "serve.step"]
    assert parents[(step[0], step[1])] is None
    serving = spans.line_of(step)
    for ev in events:
        if ev is step:
            continue
        if spans.line_of(ev) == serving:
            assert parents[(ev[0], ev[1])] in PARENT[ev[0]], ev
        elif ev[0].startswith("bg."):
            # the background threads' spans are the outermost of their
            # lines (the snapshot tick's own writes nest inside it)
            assert parents[(ev[0], ev[1])] is None, ev
    assert any(ev[0] == "bg.flush_cell" and spans.line_of(ev) != serving
               for ev in events)


# ---------------------------------------------------------- the readers
def _read(name, db=None, srv=None):
    return harness.load_reader(name).read({"db": db or {}, "srv": srv or {}})


def test_h2d_bytes_per_key_reader():
    srv = {"keys_served": 900, "writes_served": 100}
    assert _read("device.h2d_bytes_per_key", {"h2d_bytes": 5000},
                 srv) == pytest.approx(5.0)
    # a store without the counter, or a window that served nothing
    assert _read("device.h2d_bytes_per_key", {}, srv) is None
    assert _read("device.h2d_bytes_per_key", {"h2d_bytes": 5},
                 {"keys_served": 0, "writes_served": 0}) is None


def test_d2h_bytes_per_key_reader():
    srv = {"keys_served": 900, "writes_served": 100}
    assert _read("device.d2h_bytes_per_key", {"d2h_bytes": 2500},
                 srv) == pytest.approx(2.5)
    assert _read("device.d2h_bytes_per_key", {"h2d_bytes": 5}, srv) is None
    assert _read("device.d2h_bytes_per_key", {"d2h_bytes": 5},
                 {"keys_served": 0, "writes_served": 0}) is None


def test_dispatches_per_step_reader():
    db = {"lookup_dispatches": 760, "bloom_dispatches": 10}
    assert _read("device.dispatches_per_step", db,
                 {"steps_served": 10}) == pytest.approx(77.0)
    assert _read("device.dispatches_per_step", {},
                 {"steps_served": 10}) is None
    assert _read("device.dispatches_per_step", db, {}) is None


def test_wait_pct_reader():
    assert _read("serving.wait_pct", {},
                 {"step_wall_s": 2.0, "step_cpu_s": 1.5}) == \
        pytest.approx(25.0)
    assert _read("serving.wait_pct", {}, {"batches_served": 3}) is None
