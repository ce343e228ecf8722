"""Training runtime: checkpoint/restart, failure injection, elastic
resharding, straggler monitor, gradient compression, serving engine."""
import shutil
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core.checkpoint import CheckpointManager, _key
from repro.data.pipeline import ContentAddressedStore, synthetic_batch
from repro.distributed.compression import (compressed_psum,
                                           make_error_feedback_compressor,
                                           quantize_int8, dequantize_int8)
from repro.models import transformer as T
from repro.serving.engine import ServingEngine
from repro.training.loop import LoopConfig, run
from repro.training.optimizer import AdamWConfig
from repro.training.straggler import StragglerAbort, StragglerMonitor


@pytest.fixture()
def tmpdir():
    d = tempfile.mkdtemp(prefix="train-test-")
    yield d
    shutil.rmtree(d, ignore_errors=True)


CFG = get_config("llama3-8b", smoke=True)
OPT = AdamWConfig(lr=1e-3, warmup_steps=5)


def batch_fn(step):
    b = synthetic_batch(step, batch=2, seq=16, vocab=CFG.vocab)
    return {k: jnp.asarray(v) for k, v in b.items()}


def pinned_batch_fn(step):
    """Two repeating batches from a pinned seed: a learnable (memorizable)
    stream, unlike fresh random tokens whose loss floor is ln(vocab)."""
    b = synthetic_batch(step % 2, batch=2, seq=16, vocab=CFG.vocab)
    return {k: jnp.asarray(v) for k, v in b.items()}


class TestCheckpointRestart:
    def test_loss_decreases_and_checkpoints(self, tmpdir):
        out = run(CFG, OPT, LoopConfig(total_steps=12, checkpoint_every=5,
                                       seed=0),
                  pinned_batch_fn, tmpdir, log_fn=lambda s: None)
        # Smoothed tail-vs-head comparison: single-step losses are noisy.
        losses = out["losses"]
        assert np.mean(losses[-4:]) < np.mean(losses[:4])
        ckpt = CheckpointManager(tmpdir)
        assert ckpt.latest_step() == 11
        ckpt.close()

    def test_crash_resume_continues_exactly(self, tmpdir):
        with pytest.raises(RuntimeError, match="injected"):
            run(CFG, OPT, LoopConfig(total_steps=20, checkpoint_every=4,
                                     fail_at_step=10),
                batch_fn, tmpdir, log_fn=lambda s: None)
        out = run(CFG, OPT, LoopConfig(total_steps=20, checkpoint_every=4),
                  batch_fn, tmpdir, log_fn=lambda s: None)
        assert out["resumed_from"] == 8          # last checkpoint before 10
        # uninterrupted reference run matches the resumed run's tail
        d2 = tempfile.mkdtemp()
        try:
            ref = run(CFG, OPT, LoopConfig(total_steps=20,
                                           checkpoint_every=4),
                      batch_fn, d2, log_fn=lambda s: None)
            np.testing.assert_allclose(out["final_loss"], ref["final_loss"],
                                       rtol=1e-4)
        finally:
            shutil.rmtree(d2, ignore_errors=True)

    def test_checkpoint_values_roundtrip(self, tmpdir):
        params = T.init_params(CFG, jax.random.PRNGKey(1))
        ckpt = CheckpointManager(tmpdir, chunk_bytes=4096)  # force chunking
        ckpt.save(7, {"params": params})
        like = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            {"params": params})
        restored, step = ckpt.restore(like)
        assert step == 7
        for a, b in zip(jax.tree.leaves(params),
                        jax.tree.leaves(restored["params"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        ckpt.close()

    def test_step_retention_epoch_pruning(self, tmpdir):
        params = {"w": jnp.arange(4096, dtype=jnp.float32)}
        ckpt = CheckpointManager(tmpdir, keep_last=2)
        for s in range(6):
            ckpt.save(s, params)
        steps = ckpt.list_steps()
        assert 5 in steps and 4 in steps
        ckpt.close()

    def test_step_zero_is_retired_like_any_other(self, tmpdir, monkeypatch):
        """Step 0 is written as epoch 1, not as the store's never-expiring
        epoch 0: once keep_last newer steps exist, its segments drop.  The
        logs' segments are cut to 64 KiB so that a step fills several."""
        import repro.core.checkpoint as checkpoint
        real = checkpoint.WalConfig
        monkeypatch.setattr(checkpoint, "WalConfig", lambda segment_size, **kw:
                            real(segment_size=65536, **kw))
        params = {"w": jnp.arange(65536, dtype=jnp.float32)}   # 256 KiB
        ckpt = CheckpointManager(tmpdir, keep_last=2, chunk_bytes=16384,
                                 background=False)
        ckpt.save(0, params)
        ckpt.save(1, params)
        assert 0 in ckpt.list_steps()
        ckpt.save(2, params)
        ckpt.save(3, params)
        steps = ckpt.list_steps()
        assert 0 not in steps and {2, 3} <= set(steps)
        assert ckpt.db.get(_key("ckpt", 0, "w", 0), keyspace="ckpt") is None
        assert ckpt.db.metrics.segments_pruned > 0
        like = {"w": jax.ShapeDtypeStruct((65536,), jnp.float32)}
        restored, step = ckpt.restore(like)
        assert step == 3
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(params["w"]))
        ckpt.close()

    def test_elastic_restore_with_shardings(self, tmpdir):
        """Restart on a different topology: restore with explicit shardings
        (topology-agnostic checkpoint values)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        params = T.init_params(CFG, jax.random.PRNGKey(2))
        ckpt = CheckpointManager(tmpdir)
        ckpt.save(3, params)
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        shardings = jax.tree.map(
            lambda x: NamedSharding(mesh, P()), params)
        like = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
        restored, step = ckpt.restore(like, shardings=shardings)
        assert step == 3
        leaf = jax.tree.leaves(restored)[0]
        assert leaf.sharding.mesh.shape == {"data": 1, "model": 1}
        ckpt.close()


class TestStraggler:
    def test_monitor_flags_and_aborts(self):
        mon = StragglerMonitor(threshold=2.0, patience=2, action="abort",
                               ema_alpha=0.5)
        import time as _t
        for _ in range(3):                       # healthy baseline
            mon.step_start(); _t.sleep(0.01); mon.step_end(0)
        mon.step_start(); _t.sleep(0.08); mon.step_end(1)
        assert mon.slow_streak == 1
        with pytest.raises(StragglerAbort):
            mon.step_start(); _t.sleep(0.08); mon.step_end(2)
        assert len(mon.events) == 2

    def test_healthy_steps_recover_streak(self):
        mon = StragglerMonitor(threshold=2.0, patience=3)
        import time as _t
        for _ in range(3):
            mon.step_start(); _t.sleep(0.01); mon.step_end(0)
        mon.step_start(); _t.sleep(0.05); mon.step_end(1)
        mon.step_start(); _t.sleep(0.01); mon.step_end(2)
        assert mon.slow_streak == 0


class TestCompression:
    def test_quantize_roundtrip_bounded_error(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (1024,)) * 3
        q, s = quantize_int8(x)
        err = jnp.max(jnp.abs(dequantize_int8(q, s) - x))
        assert float(err) <= float(s) * 0.51

    def test_error_feedback_unbiased_over_steps(self):
        compress, init = make_error_feedback_compressor()
        g = {"w": jnp.full((256,), 0.003, jnp.float32)}
        r = init(g)
        total = jnp.zeros((256,))
        for _ in range(50):
            cg, r = compress(g, r)
            total = total + cg["w"]
        # accumulated compressed gradient ≈ accumulated true gradient
        np.testing.assert_allclose(np.asarray(total),
                                   np.full(256, 0.15), rtol=0.05)

    def test_compressed_psum_single_device(self):
        mesh = jax.make_mesh((1,), ("data",))
        from jax.sharding import PartitionSpec as P
        from jax.experimental.shard_map import shard_map
        g = {"w": jax.random.normal(jax.random.PRNGKey(1), (64,))}
        f = shard_map(lambda t: compressed_psum(t, "data"), mesh=mesh,
                      in_specs=({"w": P()},), out_specs={"w": P()})
        out = f(g)
        np.testing.assert_allclose(np.asarray(out["w"]),
                                   np.asarray(g["w"]), atol=0.05)


class TestServingEngine:
    def test_continuous_batching_and_recycling(self):
        cfg = get_config("qwen3-0.6b", smoke=True)
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        eng = ServingEngine(cfg, params, batch_slots=2, max_seq=64)
        reqs = [eng.submit(np.arange(3 + i) % cfg.vocab, max_new_tokens=5)
                for i in range(5)]
        done = eng.run_until_drained()
        assert sorted(r.rid for r in done) == sorted(r.rid for r in reqs)
        assert all(r.done for r in reqs)
        assert all(len(r.out_tokens) == 5 for r in reqs)
        assert eng.segments_recycled > 0          # epoch expiry happened
        # a second drain has nothing new to retire
        assert eng.run_until_drained() == []

    def test_greedy_matches_decode_path(self):
        """Engine output == manual prefill+decode greedy rollout."""
        from repro.models import serve as serve_mod
        cfg = get_config("llama3-8b", smoke=True)
        params = T.init_params(cfg, jax.random.PRNGKey(3))
        prompt = np.asarray([5, 7, 11], np.int32)
        eng = ServingEngine(cfg, params, batch_slots=1, max_seq=64)
        r = eng.submit(prompt, max_new_tokens=4)
        eng.run_until_drained()
        logits, cache = serve_mod.prefill(params, cfg,
                                          {"tokens": prompt[None]}, 64)
        want = [int(jnp.argmax(logits[0]))]
        for _ in range(3):
            logits, cache = serve_mod.decode_step(
                params, cfg, cache, jnp.asarray([want[-1]], jnp.int32))
            want.append(int(jnp.argmax(logits[0])))
        assert r.out_tokens == want


class TestDataPipeline:
    def test_synthetic_deterministic(self):
        a = synthetic_batch(5, 2, 16, 1000)
        b = synthetic_batch(5, 2, 16, 1000)
        np.testing.assert_array_equal(a["tokens"], b["tokens"])

    def test_content_addressed_dedup(self, tmpdir):
        store = ContentAddressedStore(tmpdir, background=False)
        toks = synthetic_batch(0, 8, 32, 1000)["tokens"]
        keys1 = store.ingest_tokens(toks, epoch=0)
        keys2 = store.ingest_tokens(toks, epoch=1)   # identical content
        assert keys1 == keys2
        assert store.inserted == 8 and store.dedup_hits == 8
        sample = store.get(keys1[0])
        np.testing.assert_array_equal(
            np.frombuffer(sample, np.int32), toks[0])
        store.close()

    def test_data_epoch_zero_expires(self, tmpdir, monkeypatch):
        """Data epoch 0 is written as store epoch 1, so it expires like the
        others (the store never expires epoch 0).  Segments are cut to
        64 KiB so that an epoch's samples fill several."""
        import repro.data.pipeline as pipeline
        real = pipeline.WalConfig
        monkeypatch.setattr(pipeline, "WalConfig", lambda segment_size, **kw:
                            real(segment_size=65536, **kw))
        store = ContentAddressedStore(tmpdir, background=False)
        keys = {ep: store.ingest_tokens(
                    synthetic_batch(ep, 1024, 32, 1000, seed=7)["tokens"],
                    epoch=ep) for ep in range(3)}
        assert store.expire_epochs_below(2) > 0
        assert all(store.get(k) is None for k in keys[0])
        assert all(store.get(k) is not None for k in keys[2])
        store.close()
