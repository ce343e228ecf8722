"""Quickstart: the Tidehunter engine as an embedded KV store.

Shows the handle-based Engine API: ``db.keyspace(name)`` handles, typed
``WriteBatch`` builders, ``ReadOptions``/``WriteOptions`` dataclasses, and
the sharded front end behind the same protocol.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import hashlib
import shutil
import tempfile

from repro.core.tidestore import (DbConfig, KeyspaceConfig, ReadOptions,
                                  ShardedTideDB, TideDB, WriteOptions)
from repro.core.tidestore.wal import WalConfig


def make_cfg() -> DbConfig:
    return DbConfig(
        keyspaces=[KeyspaceConfig("objects", n_cells=64),
                   KeyspaceConfig("meta", n_cells=8)],
        wal=WalConfig(segment_size=1 * 1024 * 1024),
    )


def main() -> None:
    path = tempfile.mkdtemp(prefix="tide-quickstart-")
    cfg = make_cfg()

    with TideDB(path, cfg) as db:
        objects = db.keyspace("objects")      # bind the keyspace once
        meta = db.keyspace("meta")

        # hash-keyed large values — the paper's target workload, tagged
        # with epochs 1-5 (untagged records, epoch 0, never expire)
        for i in range(5_000):
            key = hashlib.sha256(f"object-{i}".encode()).digest()
            objects.put(key, f"payload-{i}".encode() + bytes(1024),
                        opts=WriteOptions(epoch=1 + i // 1000))

        # probe a key from epoch 5: it must survive the epoch-<4 prune below
        key = hashlib.sha256(b"object-4234").digest()
        print("get:", objects.get(key)[:12])
        print("exists:", objects.exists(key))

        # batched reads resolve through one pipeline pass (§3.2 batched)
        probe = [hashlib.sha256(f"object-{i}".encode()).digest()
                 for i in range(4000, 4016)]
        print("multi_get:", len([v for v in objects.multi_get(probe) if v]),
              "of", len(probe))

        # scans that shouldn't churn the cache opt out of filling it
        objects.multi_get(probe, opts=ReadOptions(fill_cache=False))

        # typed atomic batch — all-or-nothing across keyspaces
        wb = objects.batch()
        wb.put(hashlib.sha256(b"tx-1").digest(), b"value")
        wb.put(hashlib.sha256(b"tx-1-meta").digest()[:32], b"pointer",
               keyspace="meta")
        db.write_batch(wb)
        print("batched meta:",
              meta.get(hashlib.sha256(b"tx-1-meta").digest()[:32]))

        # epoch pruning: drop whole WAL segments for epochs < 4 — no bytes
        # are relocated
        pruned = db.prune_epochs_below(4)
        print(f"pruned {pruned} expired segments")

        s = db.stats()
        print(f"write amplification: "
              f"{s['bytes_written_disk'] / s['bytes_written_app']:.3f}")

    # reopen: Control Region + WAL-suffix replay (crash-safe)
    with TideDB(path, cfg) as db:
        objects = db.keyspace("objects")
        print("after restart:", objects.get(key)[:12])
        print("pruned epoch gone:",
              objects.get(hashlib.sha256(b"object-42").digest()) is None)
    shutil.rmtree(path, ignore_errors=True)

    # the sharded front end speaks the same Engine protocol
    path = tempfile.mkdtemp(prefix="tide-quickstart-sharded-")
    with ShardedTideDB(path, make_cfg(), n_shards=4) as sdb:
        objects = sdb.keyspace("objects")
        ks = [hashlib.sha256(f"s{i}".encode()).digest() for i in range(2000)]
        for i, k in enumerate(ks):
            objects.put(k, b"sharded-%d" % i)
        got = objects.multi_get(ks)           # fans out across shards
        print(f"sharded multi_get: {sum(v is not None for v in got)}/2000 "
              f"across {sdb.stats()['n_shards']} shards")
    shutil.rmtree(path, ignore_errors=True)
    print("OK")


if __name__ == "__main__":
    main()
