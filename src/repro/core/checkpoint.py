"""Fault-tolerant checkpointing on the Tidehunter engine.

Checkpoints are the framework's hash-keyed, KB-to-MB-value workload — the
paper's exact target.  Each parameter shard is one WAL value keyed by
blake2b(param_path ‖ shard_index ‖ step); the Large Table maps keys to WAL
positions; Control-Region snapshots + WAL-suffix replay give crash-safe
restarts; epoch-based pruning retires old steps at segment granularity
(epoch == training step + 1: the store never expires epoch 0).

Topology-agnostic: values are keyed by (path, global_slice), so a restart
may use a different mesh — shards are re-assembled from slices and
re-sharded on load (elastic scaling).
"""
from __future__ import annotations

import hashlib
import io
import json
import threading
import time
from typing import Optional

import jax
import numpy as np

from .tidestore import DbConfig, KeyspaceConfig, TideDB
from .tidestore.wal import WalConfig


def _key(tag: str, step: int, path: str, part: int = 0) -> bytes:
    return hashlib.blake2b(f"{tag}/{step}/{path}/{part}".encode(),
                           digest_size=32).digest()


def _path_str(path) -> str:
    out = []
    for p in path:
        out.append(str(getattr(p, "key", getattr(p, "idx", p))))
    return "/".join(out)


class CheckpointManager:
    def __init__(self, directory: str, *, keep_last: int = 3,
                 chunk_bytes: int = 8 * 1024 * 1024,
                 background: bool = True):
        cfg = DbConfig(
            keyspaces=[KeyspaceConfig("ckpt", n_cells=64,
                                      dirty_flush_threshold=256),
                       KeyspaceConfig("meta", n_cells=4)],
            wal=WalConfig(segment_size=64 * 1024 * 1024,
                          background=background),
            index_wal=WalConfig(segment_size=8 * 1024 * 1024,
                                background=background),
            background_snapshots=background,
            cache_bytes=0,
        )
        self.db = TideDB(directory, cfg)
        self.keep_last = keep_last
        self.chunk_bytes = chunk_bytes
        self._lock = threading.Lock()
        self._async_thread: Optional[threading.Thread] = None

    # ---------------------------------------------------------------- save
    def save(self, step: int, state, wait: bool = True) -> None:
        """Async by default: device→host copy happens synchronously (cheap,
        sharded), WAL writes run in a background thread (the paper's
        synchronous/asynchronous split applied to checkpointing)."""
        host_state = jax.tree.map(np.asarray, state)
        if self._async_thread is not None:
            self._async_thread.join()

        def write():
            self._write_step(step, host_state)

        self._async_thread = threading.Thread(target=write, daemon=True)
        self._async_thread.start()
        if wait:
            self._async_thread.join()

    def _write_step(self, step: int, host_state) -> None:
        with self._lock:
            leaves = jax.tree_util.tree_flatten_with_path(host_state)[0]
            manifest = []
            for path, leaf in leaves:
                pstr = _path_str(path)
                buf = np.ascontiguousarray(leaf)
                raw = buf.tobytes()
                nparts = max(1, (len(raw) + self.chunk_bytes - 1)
                             // self.chunk_bytes)
                for part in range(nparts):
                    chunk = raw[part * self.chunk_bytes:
                                (part + 1) * self.chunk_bytes]
                    self.db.put(_key("ckpt", step, pstr, part), chunk,
                                keyspace="ckpt", epoch=step + 1)
                manifest.append({"path": pstr, "dtype": str(buf.dtype),
                                 "shape": list(buf.shape), "parts": nparts})
            self.db.put(_key("manifest", step, "", 0),
                        json.dumps({"step": step, "leaves": manifest,
                                    "time": time.time()}).encode(),
                        keyspace="meta", epoch=step + 1)
            self.db.put(_key("latest", 0, "", 0),
                        str(step).encode(), keyspace="meta", epoch=step + 1)
            self.db.flush()
            self._prune(step)

    def _prune(self, newest_step: int) -> None:
        """Epoch pruning (§4.4): whole WAL segments whose steps all fall
        below the oldest kept step are dropped — no value is rewritten.
        Step s is written as epoch s + 1."""
        keep = sorted(self.list_steps())[-self.keep_last:]
        if keep:
            self.db.prune_epochs_below(keep[0] + 1)

    # ---------------------------------------------------------------- load
    def latest_step(self) -> Optional[int]:
        raw = self.db.get(_key("latest", 0, "", 0), keyspace="meta")
        return int(raw) if raw is not None else None

    def list_steps(self) -> list[int]:
        steps = []
        latest = self.latest_step()
        if latest is None:
            return steps
        for s in range(max(0, latest - 100), latest + 1):
            if self.db.exists(_key("manifest", s, "", 0), keyspace="meta"):
                steps.append(s)
        return steps

    def restore(self, like, step: Optional[int] = None,
                shardings=None):
        """Rebuild the pytree ``like`` (shapes/dtypes template).  When
        ``shardings`` is given, leaves are device_put with the new topology
        (elastic restart on a different mesh)."""
        if self._async_thread is not None:
            self._async_thread.join()
        step = step if step is not None else self.latest_step()
        if step is None:
            return None, None
        raw = self.db.get(_key("manifest", step, "", 0), keyspace="meta")
        if raw is None:
            return None, None
        manifest = json.loads(raw)
        by_path = {m["path"]: m for m in manifest["leaves"]}

        def load(path, leaf):
            pstr = _path_str(path)
            m = by_path[pstr]
            parts = []
            for part in range(m["parts"]):
                chunk = self.db.get(_key("ckpt", step, pstr, part),
                                    keyspace="ckpt")
                if chunk is None:
                    raise KeyError(f"missing checkpoint chunk {pstr}/{part}")
                parts.append(chunk)
            arr = np.frombuffer(b"".join(parts), dtype=m["dtype"]).reshape(
                m["shape"])
            return arr

        host = jax.tree_util.tree_map_with_path(load, like)
        if shardings is not None:
            host = jax.tree.map(jax.device_put, host, shardings)
        else:
            host = jax.tree.map(jax.numpy.asarray, host)
        return host, step

    def stats(self) -> dict:
        return self.db.stats()

    def close(self) -> None:
        if self._async_thread is not None:
            self._async_thread.join()
        self.db.close()
