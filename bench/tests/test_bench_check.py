"""The comparison that decides ``correct``, run end to end on the CPU at
4,096 records: a sound run reads correct, and each fault a cell can have,
planted in the timed path underneath the harness, reads not correct, as
does the control (bench/control.py).

The harness's look for a chip lives in ``bench/run.py``; these tests call
the run itself, so nothing in the harness is switched off for them.
"""
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

import functools  # noqa: E402

from bench import control, harness  # noqa: E402
from repro.core.tidestore import TideDB  # noqa: E402

EXISTS, YCSB, GET = ("kv1k-uniform.exists", "ycsb-1k.b-zipfian",
                     "kv1k-uniform.get")


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    # the compile cache is the chip runs' business; keep this worker's
    # JAX configuration as the other test files expect it
    monkeypatch.setattr("repro.compile_cache.enable", lambda checkout: None)


def _run(name, seed=2**31 + 5, engine_wrap=None, seconds=0.3):
    cell = harness.load_cell(name)
    cell.config["records"] = 4096
    cell.config["store"]["cache_bytes"] = 256 * 1024
    cell.workload.update(outstanding=512, sequence_blocks=8)
    cell.workload["warmup"]["requests"] = 512
    lines = []
    res = harness.run_cell(cell, seed, seconds, False,
                           t_process=time.perf_counter(),
                           log=lambda m, err=False: lines.append((err, m)),
                           engine_wrap=engine_wrap)
    return res, lines


def _checks(res):
    return {k: v["value"] for k, v in res["checks"].items()}


@pytest.mark.parametrize("name", [EXISTS, YCSB, GET])
def test_a_sound_run_is_correct(name):
    res, lines = _run(name)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert all(v == 0 for v in _checks(res).values())
    if name == YCSB:
        assert "readback_wrong" in res["checks"]
    # the compared numbers, each beside its limit, are stderr's last lines
    err = [m for e, m in lines if e]
    assert err and all(m.startswith("check ") and " limit=" in m
                       for m in err)
    assert list(res)[-1] == "checks"


def test_writes_acknowledged_but_not_landed_fail(monkeypatch):
    """A write step that returns the store's state unchanged."""
    monkeypatch.setattr(TideDB, "put_many",
                        lambda self, items, **k: [0] * len(list(items)))
    res, _ = _run(YCSB)
    assert res["correct"] is False
    assert _checks(res)["readback_wrong"] > 0


@pytest.mark.parametrize("name", [EXISTS, YCSB, GET])
def test_half_of_each_batch_left_out_fails(monkeypatch, name):
    """The engine answers the first half of each batch and leaves the rest
    at their defaults."""
    real_get, real_exists = TideDB.multi_get, TideDB.multi_exists

    def half(real, empty):
        def fn(self, keys, **k):
            n = len(keys) // 2
            return real(self, keys[:n], **k) + [empty] * (len(keys) - n)
        return fn

    monkeypatch.setattr(TideDB, "multi_get", half(real_get, None))
    monkeypatch.setattr(TideDB, "multi_exists", half(real_exists, False))
    res, _ = _run(name)
    assert res["correct"] is False
    assert _checks(res)["wrong_answers"] > 0


@pytest.mark.parametrize("name", [EXISTS, YCSB, GET])
def test_one_answer_altered_per_batch_fails(monkeypatch, name):
    """One answer of each batch altered where the engine produces it."""
    real_get, real_exists = TideDB.multi_get, TideDB.multi_exists

    def get(self, keys, **k):
        out = real_get(self, keys, **k)
        if out and out[0] is not None:
            out[0] = bytes([out[0][0] ^ 1]) + out[0][1:]
        return out

    def exists(self, keys, **k):
        out = real_exists(self, keys, **k)
        if out:
            out[0] = not out[0]
        return out

    monkeypatch.setattr(TideDB, "multi_get", get)
    monkeypatch.setattr(TideDB, "multi_exists", exists)
    res, _ = _run(name)
    assert res["correct"] is False
    assert _checks(res)["wrong_answers"] > 0


@pytest.mark.parametrize("name", [EXISTS, YCSB, GET])
def test_the_control_reads_not_correct(name):
    """The reference in the store's place, answering exists from a Bloom
    filter alone and reads from the loaded records alone."""
    # an exists is wrong only on a false positive (~0.07 % of absent keys
    # at this size), so the window runs long enough for dozens of them; a
    # get of a loaded key only where another shares its prefix: 2 bytes
    # give 4,096 keys about as many such pairs (~128) as 4 bytes give 1 M
    # keys (~116)
    res, _ = _run(name, seconds=2.0, engine_wrap=functools.partial(
        control.ControlEngine, prefix_bytes=2 if name == GET else 4))
    assert res["correct"] is False
    assert _checks(res)["wrong_answers"] > 0


def test_control_bloom_has_no_false_negatives_and_some_positives():
    keys = [i.to_bytes(32, "big") for i in range(5000)]
    others = [(i + 10**6).to_bytes(32, "big") for i in range(50000)]
    bloom = control.BloomOnly(keys)
    assert bloom.might_contain(keys).all()
    fp = bloom.might_contain(others).mean()
    assert 0 < fp < 0.01
