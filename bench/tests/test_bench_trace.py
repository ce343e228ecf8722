"""The reduction from a profiler trace to the benchmark's device numbers
(bench/trace.py), on a hand-made trace whose answers are known and on a
small trace recorded on a TPU v5e."""
import glob
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness, roofline, trace  # noqa: E402

US = 1000                                  # events are in nanoseconds
TPU = "/device:TPU:0"


def _ev(name, s, e):
    return [name, s * US, e * US]


HAND = {
    "host": [_ev("bench.window", 0, 1000), _ev("bench.topup", 0, 100),
             _ev("serving.step", 100, 1000),
             _ev("engine.multi_exists", 150, 900),
             _ev("form.probe_cells_batch", 200, 300),
             _ev("form.lookup_indices_batch", 400, 800),
             _ev("bench.topup", 1000, 1100)],
    "device": {TPU: {
        "modules": [_ev("jit_bloom_check_ragged(1)", 250, 290),
                    _ev("jit_optimistic_lookup(2)", 500, 520),
                    _ev("jit_optimistic_lookup(3)", 600, 640),
                    _ev("jit_optimistic_lookup(4)", 1200, 1300)],
        "ops": [_ev("fusion", 250, 270), _ev("gather", 270, 290),
                _ev("custom-call", 505, 520), _ev("custom-call", 605, 640),
                _ev("custom-call", 1200, 1300)]}},
}


def test_hand_made_trace():
    r = trace.reduce(HAND)
    assert r["window_s"] == pytest.approx(1000e-6)
    assert r["busy_s"] == pytest.approx(90e-6)
    assert r["modules"]["jit_optimistic_lookup"]["runs"] == 2
    assert r["modules"]["jit_optimistic_lookup"]["seconds"] == \
        pytest.approx(60e-6)
    assert r["modules"]["jit_bloom_check_ragged"]["seconds"] == \
        pytest.approx(40e-6)
    ops = dict(r["device_ops"])
    assert ops["jit_optimistic_lookup/custom-call"] == pytest.approx(50e-6)
    assert ops["jit_bloom_check_ragged/gather"] == pytest.approx(20e-6)
    idle = dict(r["idle_gaps"])
    want = {"bench.topup": 100, "serving.step": 150,
            "engine.multi_exists": 250, "form.probe_cells_batch": 60,
            "form.lookup_indices_batch": 350}
    assert idle == pytest.approx({k: v * 1e-6 for k, v in want.items()})
    assert sum(idle.values()) + r["busy_s"] == pytest.approx(r["window_s"])


def test_union_and_idle_outside_spans():
    assert trace.union([(5, 9), (1, 3), (2, 4), (9, 10)]) == [[1, 4], [5, 10]]
    idle = trace.idle_by_span([[0, 10 * US]], [["a", 2 * US, 4 * US]])
    assert idle == pytest.approx({trace.OUTSIDE: 8e-6, "a": 2e-6})


def test_trace_names():
    assert trace.op_name("%fusion.3 = s32[128]{0} fusion(u32[1] %k), "
                         "kind=kLoop") == "fusion.3"
    assert trace.op_name("gather") == "gather"
    assert trace.module_name("jit_optimistic_lookup(12)") == \
        "jit_optimistic_lookup"


def test_no_window_or_no_device_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce({"host": [], "device": HAND["device"]})
    with pytest.raises(ValueError):
        trace.reduce({"host": HAND["host"], "device": {}})


RECORDED = sorted(glob.glob(os.path.join(ROOT, "bench", "tests", "data",
                                         "trace_*.json")))


@pytest.mark.parametrize("path", RECORDED,
                         ids=[os.path.basename(p) for p in RECORDED])
def test_recorded_chip_trace(path):
    """A few steps of a cell recorded on one TPU v5e, reduced to the
    metrics the cell reports."""
    with open(path) as f:
        rec = json.load(f)
    r = trace.reduce(rec["events"])
    assert 0 < r["busy_s"] < r["window_s"]
    idle = sum(s for _, s in trace.idle_by_span(
        *_gaps_and_spans(rec["events"])).items())
    assert idle + r["busy_s"] == pytest.approx(r["window_s"], rel=1e-6)
    want = rec["expect"]["modules"]
    assert set(r["modules"]) == set(want)
    for name, m in want.items():
        assert r["modules"][name]["runs"] == m["runs"]
        assert r["modules"][name]["seconds"] == pytest.approx(m["seconds"])
    cell = harness.load_cell(rec["cell"])
    ctx = {"trace": r, "calls": {tuple(k.split(":")): v
                                 for k, v in rec["calls"].items()},
           "db": rec["db"], "srv": rec["srv"], "done": rec["done"],
           # the recordings carry counts, not each request's latency
           "latency_ms": {k: np.zeros(0) for k in rec["done"]},
           "config": cell.config, "peak": roofline.peaks("TPU v5 lite")}
    read = {m["name"]: harness.load_reader(m["name"]).read(ctx)
            for m in cell.per_layer}
    # at 100,000 records every parsed blob fits the blob cache, so the
    # Bloom probe (which memoized cells skip) has nothing to read there
    assert read["device.idle_pct"] is not None
    assert read[next(n for n in read
                     if n.startswith("optimistic_lookup_roofline"))]
    for m in cell.per_layer:
        if m["unit"] == "%" and read[m["name"]] is not None:
            assert 0 <= read[m["name"]] <= 100, (m["name"], read[m["name"]])


def _gaps_and_spans(events):
    t0, t1 = next((s, e) for n, s, e in events["host"]
                  if n == trace.WINDOW_SPAN)
    busy = trace.union((max(s, t0), min(e, t1))
                       for p in events["device"].values()
                       for _, s, e in p["ops"] if e > t0 and s < t1)
    gaps, cur = [], t0
    for s, e in busy:
        if s > cur:
            gaps.append([cur, s])
        cur = max(cur, e)
    if cur < t1:
        gaps.append([cur, t1])
    spans = [[n, max(s, t0), min(e, t1)] for n, s, e in events["host"]
             if n != trace.WINDOW_SPAN and e > t0 and s < t1]
    return gaps, spans


@pytest.mark.parametrize("kind,other", [("get", "put"), ("put", "get")])
def test_tail_readers_take_the_percentile_of_their_kind(kind, other):
    read = harness.load_reader(f"serving.{kind}_p99_ms").read
    lat = {kind: np.arange(1, 101, dtype=float), other: np.zeros(0)}
    assert read({"latency_ms": lat}) == pytest.approx(99.01)
    lat = {kind: np.zeros(0), other: np.arange(1, 101, dtype=float)}
    assert read({"latency_ms": lat}) is None
