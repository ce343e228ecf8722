"""The validator deployment through the harness on the CPU: a store that
holds untagged records (the live object set) and ingests epoch-tagged
ones keeps every untagged record and retires the expired epochs, by
expiry alone; the cell ``sui-validator.epoch-ingest`` cut to a few
thousand records runs end to end; and the readers of the prune layer.

``data/validator-4k*.json`` is the validator shape at 4,096 loaded
records (not a cell): 1 KiB records, the newest two epochs retained,
new keys put in epochs of 1,024 requests, reads of recently put and of
loaded keys, exists of absent keys.
"""
import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import harness, traffic  # noqa: E402
from repro.core.tidestore import TideDB  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
E2E = [{"name": "setup_s", "unit": "s"},
       {"name": "ops_per_s", "unit": "ops/s"}]
CELL = "sui-validator.epoch-ingest"
SEEDS = [2**31 + 5, 7, 2**33 + 1, 3417000001, 3417000002, 3417000003,
         3417000004, 2**40 + 17, 11, 2**32 - 1]
PRUNE = ["prune.stall_pct", "prune.relocated_bytes_per_put",
         "prune.segments_dropped_per_epoch"]


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    monkeypatch.setattr("repro.compile_cache.enable", lambda checkout: None)


@pytest.fixture()
def store_counts(monkeypatch):
    """The store's reclamation counters at each close, in order."""
    closes = []
    real = TideDB.close

    def close(self, *a, **kw):
        s = self.stats()
        closes.append({k: s[k] for k in ("segments_pruned",
                                         "relocated_entries")})
        return real(self, *a, **kw)

    monkeypatch.setattr(TideDB, "close", close)
    return closes


def _run(cell, seed, seconds=1.0, traced=False):
    res = harness.run_cell(cell, seed, seconds, traced,
                           t_process=time.perf_counter(),
                           log=lambda m, err=False: None)
    return res, {k: v["value"] for k, v in res["checks"].items()}


def _validator_4k():
    with open(os.path.join(DATA, "validator-4k.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(DATA, "validator-4k.ingest.json")) as f:
        wl = json.load(f)
    assert cfg["records"] == 4096 and wl["keys"]["loaded_share"] > 0
    return harness.Cell("validator-4k.ingest", 1, cfg, wl, E2E, [])


@pytest.mark.parametrize("seed", SEEDS)
def test_loaded_records_survive_epoch_pruning(seed, store_counts):
    """4,096 untagged loaded records beside epochs that expire: all read
    back, no expired key is found, and expiry reclaims without relocating
    a record.  (Before untagged records were kept, every loaded record
    was lost.)"""
    res, checks = _run(_validator_4k(), seed)
    assert res["correct"] is True, checks
    assert checks["readback_wrong"] == 0
    assert checks["expired_present"] == 0
    assert checks["epoch_misaligned"] == 0 and checks["wrong_answers"] == 0
    served = store_counts[1]             # the served store (after the load)
    assert served["segments_pruned"] > 0
    assert served["relocated_entries"] == 0


def _cut_cell():
    """The cell as BENCHMARK.json has it, cut to 4,096 records, 16 cells,
    256 outstanding, 1,024-request epochs, a sequence of 4 epochs and
    256 KiB segments; its mix, chooser, retention and prune policy kept."""
    cell = harness.load_cell(CELL)
    cfg, wl = cell.config, cell.workload
    cfg.update(records=4096, epoch_length=1024)
    cfg["keyspace"]["n_cells"] = 16
    cfg["store"].update(cache_bytes=256 * 1024, blob_cache_bytes=1 << 20)
    cfg["store"]["wal"]["segment_size"] = 256 * 1024
    wl.update(outstanding=256, epoch_requests=1024, sequence_blocks=16)
    wl["warmup"]["requests"] = 1024
    return cell


def test_the_cell_cut_small_runs_through_the_harness(store_counts,
                                                      monkeypatch):
    """A traced run, so the prune readers read the window's counters; the
    CPU's trace has no TPU plane and the CPU no peaks, so the device's
    reduction and peaks are empty."""
    monkeypatch.setattr(harness.trace_mod, "load", lambda path, spans: None)
    monkeypatch.setattr(harness.trace_mod, "reduce", lambda events: {
        "busy_s": 0.0, "window_s": 1.0, "device_ops": {}, "idle_gaps": {},
        "modules": {}})
    monkeypatch.setattr(harness.roofline, "peaks", lambda kind: {})
    cell = _cut_cell()
    assert cell.chips == 1
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "ops_per_s"]
    assert [m["name"] for m in cell.per_layer] == PRUNE
    res, checks = _run(cell, 3417000101, traced=True)
    assert res["correct"] is True, checks
    assert res["failed"] == 0
    # the window outruns the sequence, as the chip's window outruns the
    # cell's: a cycle's new keys are put again in a later epoch
    assert res["attempted"] > 256 * 16
    assert set(checks) >= {"readback_wrong", "expired_present",
                           "epoch_misaligned"}
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["prune.segments_dropped_per_epoch"] > 0
    assert m["prune.relocated_bytes_per_put"] == 0
    assert 0 < m["prune.stall_pct"] < 100
    assert store_counts[1]["relocated_entries"] == 0


def test_the_configuration_matches_its_traffic():
    cell = harness.load_cell(CELL)
    cfg, wl = cell.config, cell.workload
    assert cfg["records"] == 1_000_000
    assert (cfg["key_bytes"], cfg["value_bytes"]) == (32, 1024)
    assert cfg["epoch_length"] == wl["epoch_requests"] == 65536
    assert traffic.epoch_requests(cfg, wl) == 65536
    opts = harness.store_config(cfg)
    assert opts.prune.retain_epochs == 2
    assert opts.prune.space_amp_trigger == 3.0
    assert opts.wal.segment_size == 4 * 1024 * 1024
    assert set(cfg["reduced"]) <= set(cfg["why_reduced"])
    # 16 epochs a cycle of the sequence, which a window may outrun
    assert wl["outstanding"] * wl["sequence_blocks"] == (
        16 * wl["epoch_requests"])


def _reader(name):
    return harness.load_reader(name)


@pytest.mark.parametrize("srv, want", [
    ({"prune_step_s": 0.5, "step_wall_s": 10.0}, 5.0),
    ({"step_wall_s": 10.0}, None),                 # server without the timer
    ({"prune_step_s": 0.0, "step_wall_s": 0.0}, None),
])
def test_stall_pct_reader(srv, want):
    assert _reader("prune.stall_pct").read({"srv": srv}) == want


@pytest.mark.parametrize("db, srv, want", [
    ({"relocated_bytes": 512}, {"write_bytes": 4096}, 0.125),
    ({"relocated_bytes": 0}, {"write_bytes": 4096}, 0.0),
    ({}, {"write_bytes": 4096}, None),
    ({"relocated_bytes": 0}, {"write_bytes": 0}, None),
])
def test_relocated_bytes_per_put_reader(db, srv, want):
    ctx = {"db": db, "srv": srv}
    assert _reader("prune.relocated_bytes_per_put").read(ctx) == want


@pytest.mark.parametrize("db, srv, want", [
    ({"segments_pruned": 12}, {"write_epoch": 2}, 6.0),
    ({}, {"write_epoch": 2}, None),
    ({"segments_pruned": 3}, {"write_epoch": 0}, None),
    ({"segments_pruned": 3}, {}, None),            # server without epochs
])
def test_segments_dropped_per_epoch_reader(db, srv, want):
    ctx = {"db": db, "srv": srv, "config": {"epoch_length": 1}}
    assert _reader("prune.segments_dropped_per_epoch").read(ctx) == want
