"""Epoch-tagged writes and their pruning, through the harness on the CPU.

``data/validator-4k.json`` and ``data/validator-4k.ingest.json`` are a
validator-shaped configuration and traffic (not cells of BENCHMARK.json):
1 KiB records, the newest two epochs retained, new keys put in epochs of
1,024 requests, reads of keys put recently, exists of absent keys.  The
run goes through ``harness.run_cell``, so the server is built with the
configuration's ``PruneOptions`` and reclaims between its stages.

The store drops untagged records with the segments that hold only them
once the epoch floor passes 0 (PERF.md §7), which the reference counts as
a fault; the sound runs here therefore start from an empty store, as a
validator from genesis does.

The cells of BENCHMARK.json carry no epochs: their traffic and their
server stay as they were, which the last tests pin.
"""
import hashlib
import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import harness, traffic  # noqa: E402
from repro.core.tidestore import TideDB  # noqa: E402
from repro.serving.engine import KvBatchServer  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
E2E = [{"name": "setup_s", "unit": "s"},
       {"name": "ops_per_s", "unit": "ops/s"}]
PUT = traffic.OPS.index("put")
SEEDS = [2**31 + 5, 7, 2**33 + 1]


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    monkeypatch.setattr("repro.compile_cache.enable", lambda checkout: None)


def _cell(genesis=True):
    with open(os.path.join(DATA, "validator-4k.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(DATA, "validator-4k.ingest.json")) as f:
        wl = json.load(f)
    if genesis:
        cfg["records"] = 0
        wl["keys"]["loaded_share"] = 0.0
    return harness.Cell("validator-4k.ingest", 1, cfg, wl, E2E, [])


def _run(cell, seed=SEEDS[0], seconds=1.0):
    res = harness.run_cell(cell, seed, seconds, False,
                           t_process=time.perf_counter(),
                           log=lambda m, err=False: None)
    return res, {k: v["value"] for k, v in res["checks"].items()}


@pytest.mark.parametrize("seed", SEEDS)
def test_a_sound_run_is_correct(seed):
    res, checks = _run(_cell(), seed)
    assert res["correct"] is True, checks
    assert res["attempted"] > 0 and res["failed"] == 0
    assert checks["epoch_misaligned"] == 0
    assert checks["expired_present"] == 0
    assert checks["readback_wrong"] == 0


def test_a_server_without_prune_opts_keeps_expired_epochs(monkeypatch):
    real = KvBatchServer.__init__

    def no_prune(self, db, **kw):
        real(self, db, **dict(kw, prune_opts=None))

    monkeypatch.setattr(KvBatchServer, "__init__", no_prune)
    res, checks = _run(_cell())
    assert res["correct"] is False
    assert checks["expired_present"] > 0
    assert checks["wrong_answers"] == 0 and checks["readback_wrong"] == 0


def test_one_dropped_put_of_a_retained_epoch_fails(monkeypatch):
    """The window is one step, the fifth, which alone writes epoch 2 (the
    warm-up's 1,024 requests are epoch 1): the first put it writes is
    acknowledged and dropped, and the final floor retains epoch 2."""
    real = TideDB.put_many
    dropped = []

    def put_many(self, items, **kw):
        items = list(items)
        opts = kw.get("opts")
        if opts is not None and opts.epoch == 2 and not dropped:
            dropped.append(items[0][0])
            return [0] + real(self, items[1:], **kw)
        return real(self, items, **kw)

    monkeypatch.setattr(TideDB, "put_many", put_many)
    res, checks = _run(_cell(), seconds=0.0)
    assert len(dropped) == 1
    assert res["correct"] is False
    assert checks["readback_wrong"] == 1


def _epoch(i, e):
    return 1 + i // e


@pytest.mark.parametrize("genesis", [False, True])
def test_reads_target_only_keys_the_store_retains(genesis):
    """Every read of a present key asks for a loaded record or for a key
    put earlier in the same cycle whose epoch is at or above the floor
    that request's own epoch sets; reads of absent keys ask for keys
    never loaded and never put."""
    cell = _cell(genesis)
    cfg, wl = cell.config, cell.workload
    e = wl["epoch_requests"]
    retain = cfg["store"]["prune"]["retain_epochs"]
    data = traffic.make_dataset(cfg, 11)
    seq = traffic.make_sequence(cfg, wl, data, 11)
    loaded = {k: r for r, k in enumerate(data.keys)}
    last_put: dict = {}
    put_keys = {k for k, o in zip(seq.key, seq.op) if o == PUT}
    from_loaded = recent = absent = 0
    for j, (k, o, r) in enumerate(zip(seq.key, seq.op.tolist(),
                                      seq.record.tolist())):
        if o == PUT:
            assert k not in loaded
            last_put[k] = j
            continue
        if r >= 0:
            assert data.keys[r] == k
            from_loaded += 1
        elif k in last_put:
            # every later cycle repeats the sequence, so the same holds
            # with every position shifted by a whole number of epochs
            floor = _epoch(j, e) - retain + 1
            assert _epoch(last_put[k], e) >= floor
            recent += 1
        else:
            assert k not in loaded and k not in put_keys
            absent += 1
    assert recent > 0 and absent > 0
    if genesis:
        assert from_loaded == 0
    else:
        share = from_loaded / (from_loaded + recent)
        assert share == pytest.approx(wl["keys"]["loaded_share"], abs=0.03)


@pytest.mark.parametrize("bad", [{"epoch_requests": 1000},
                                 {"epoch_requests": 256 * 3},
                                 {"epoch_requests": None},
                                 {"keys": {"distribution": "recent",
                                           "within_epochs": 3,
                                           "loaded_share": 0.0}}])
def test_epochs_that_do_not_fit_are_refused(bad):
    cell = _cell()
    cell.workload.update(bad)
    data = traffic.make_dataset(cell.config, 1)
    with pytest.raises(ValueError):
        traffic.make_sequence(cell.config, cell.workload, data, 1)


# ------------------------------------------- the cells without epochs
CELLS = ["kv1k-uniform.exists", "ycsb-1k.b-zipfian"]
# make_sequence's output at 4,096 records, 512 outstanding, 4 blocks and
# seed 2**31 + 16, as the commit before epochs came to the harness made it
PINNED = {
    "kv1k-uniform.exists":
        "8037ab03f841a3cb13cc4bcacb7dc611dabb203b803a66a3d51db48c5c2aa426",
    "ycsb-1k.b-zipfian":
        "f428d63252784ce76ea88e96b58505c7b0136864c4a4abca1f92ddbf650cb125",
}


def _digest(seq) -> str:
    h = hashlib.sha256()
    h.update(seq.op.astype("int8").tobytes())
    h.update(seq.record.astype("<i8").tobytes())
    h.update(b"".join(seq.key))
    h.update(b"".join(v for v in seq.value if v is not None))
    return h.hexdigest()


@pytest.mark.parametrize("name", CELLS)
def test_the_cells_sequences_are_as_before(name):
    cell = harness.load_cell(name)
    cell.config["records"] = 4096
    cell.workload.update(outstanding=512, sequence_blocks=4)
    seed = 2**31 + 16
    data = traffic.make_dataset(cell.config, seed)
    seq = traffic.make_sequence(cell.config, cell.workload, data, seed)
    assert _digest(seq) == PINNED[name]


@pytest.mark.parametrize("name", CELLS)
def test_the_cells_serve_without_epochs_or_pruning(monkeypatch, name):
    real = KvBatchServer.__init__
    servers = []

    def record(self, db, **kw):
        real(self, db, **kw)
        servers.append(self)

    monkeypatch.setattr(KvBatchServer, "__init__", record)
    cell = harness.load_cell(name)
    cell.config["records"] = 4096
    cell.config["store"]["cache_bytes"] = 256 * 1024
    cell.workload.update(outstanding=512, sequence_blocks=8)
    cell.workload["warmup"]["requests"] = 512
    res, checks = _run(cell, seconds=0.3)
    assert res["correct"] is True
    (srv,) = servers
    assert srv.prune_opts is None and srv.write_opts is None
    assert srv.prune_steps == 0
    assert not {"epoch_misaligned", "expired_present"} & set(checks)
    assert np.isfinite(res["metrics"]["ops_per_s"]["value"])
