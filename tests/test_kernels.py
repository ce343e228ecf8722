"""Pallas kernel validation: interpret-mode vs pure-jnp oracles, with
shape/dtype sweeps and hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import HealthCheck, given, settings, st

from repro.core.tidestore.bloom import _probe_host
from repro.kernels.bloom_check.kernel import bloom_check_ragged
from repro.kernels.bloom_check.ref import bloom_add_ref
from repro.kernels.optimistic_lookup.kernel import optimistic_lookup
from repro.kernels.optimistic_lookup.ops import lookup_indices_batch
from repro.kernels.optimistic_lookup.ref import optimistic_lookup_ref
from repro.kernels.tide_attention.kernel import tide_attention
from repro.kernels.tide_attention.ref import tide_attention_ref

SETTINGS = settings(max_examples=15, deadline=None,
                    suppress_health_check=[HealthCheck.too_slow])


def bloom_check(h1, h2, bits, *, k=7, nbits=None):
    """One filter probed through the ragged device probe: every query on
    one cell at word 0."""
    q = h1.shape[0]
    nbits = nbits if nbits is not None else bits.shape[0] * 32
    return bloom_check_ragged(h1, h2, jnp.zeros(q, jnp.int32),
                              jnp.full(q, nbits, jnp.uint32), bits, k=k)


def bloom_check_host(h1, h2, bits, *, k=7, nbits=None):
    """The same probe through the store's numpy pass — the reference."""
    q = np.asarray(h1).shape[0]
    nbits = nbits if nbits is not None else np.asarray(bits).shape[0] * 32
    return _probe_host(np.asarray(h1, np.uint32), np.asarray(h2, np.uint32),
                       np.zeros(q, np.int32), np.full(q, nbits, np.uint32),
                       np.asarray(bits, np.uint32), k)


def _mk_arena(key, B, NB, blk, KH, dk, dv, dtype):
    ks = jax.random.split(key, 4)
    ak = jax.random.normal(ks[0], (B, NB, blk, KH, dk), jnp.float32)
    av = jax.random.normal(ks[1], (B, NB, blk, KH, dv), jnp.float32)
    table = jnp.stack([
        jax.random.permutation(jax.random.fold_in(ks[2], b), NB)
        for b in range(B)]).astype(jnp.int32)
    return ak.astype(dtype), av.astype(dtype), table


class TestTideAttention:
    @pytest.mark.parametrize("B,H,KH,dk,dv,NB,blk", [
        (2, 8, 4, 64, 64, 4, 32),        # GQA
        (1, 4, 1, 128, 128, 3, 128),     # MQA (griffin), MXU-aligned block
        (3, 4, 4, 32, 32, 2, 16),        # MHA
        (2, 16, 2, 64, 32, 5, 64),       # dk != dv
    ])
    def test_shapes_vs_ref(self, B, H, KH, dk, dv, NB, blk):
        key = jax.random.PRNGKey(B * 131 + H)
        q = jax.random.normal(key, (B, H, dk), jnp.float32)
        ak, av, table = _mk_arena(key, B, NB, blk, KH, dk, dv, jnp.float32)
        lens = jnp.asarray(
            np.random.default_rng(0).integers(1, NB * blk + 1, B), jnp.int32)
        live = jnp.zeros((B,), jnp.int32)
        out = tide_attention(q, ak, av, table, lens, live, interpret=True)
        ref = tide_attention_ref(q, ak, av, table, lens, live)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        key = jax.random.PRNGKey(3)
        B, H, KH, dk, dv, NB, blk = 2, 8, 4, 64, 64, 4, 32
        q = jax.random.normal(key, (B, H, dk), jnp.float32).astype(dtype)
        ak, av, table = _mk_arena(key, B, NB, blk, KH, dk, dv, dtype)
        lens = jnp.array([120, 77], jnp.int32)
        live = jnp.array([0, 16], jnp.int32)
        out = tide_attention(q, ak, av, table, lens, live, interpret=True)
        ref = tide_attention_ref(q, ak, av, table, lens, live)
        tol = 1e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=tol, atol=tol)

    def test_epoch_pruning_matches_window(self):
        """first_live masking == attending only to live segments."""
        key = jax.random.PRNGKey(9)
        B, H, KH, dk, dv, NB, blk = 2, 4, 2, 32, 32, 6, 16
        q = jax.random.normal(key, (B, H, dk), jnp.float32)
        ak, av, table = _mk_arena(key, B, NB, blk, KH, dk, dv, jnp.float32)
        lens = jnp.array([90, 96], jnp.int32)
        live = jnp.array([32, 48], jnp.int32)
        out = tide_attention(q, ak, av, table, lens, live, interpret=True)
        # oracle: physically zeroing pruned blocks must give the same result
        ref = tide_attention_ref(q, ak, av, table, lens, live)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_sliding_window(self):
        key = jax.random.PRNGKey(11)
        B, H, KH, dk, dv, NB, blk = 2, 4, 4, 32, 32, 8, 16
        q = jax.random.normal(key, (B, H, dk), jnp.float32)
        ak, av, table = _mk_arena(key, B, NB, blk, KH, dk, dv, jnp.float32)
        lens = jnp.array([128, 70], jnp.int32)
        live = jnp.zeros((B,), jnp.int32)
        for w in (16, 48, 100):
            out = tide_attention(q, ak, av, table, lens, live, window=w,
                                 interpret=True)
            ref = tide_attention_ref(q, ak, av, table, lens, live, window=w)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=2e-5, atol=2e-5)

    @given(seed=st.integers(0, 2**31 - 1),
           lens=st.lists(st.integers(1, 128), min_size=2, max_size=2))
    @SETTINGS
    def test_property_random_tables(self, seed, lens):
        key = jax.random.PRNGKey(seed)
        B, H, KH, dk, dv, NB, blk = 2, 4, 2, 32, 32, 4, 32
        q = jax.random.normal(key, (B, H, dk), jnp.float32)
        ak, av, table = _mk_arena(key, B, NB, blk, KH, dk, dv, jnp.float32)
        lens = jnp.asarray(lens, jnp.int32)
        live = jnp.zeros((B,), jnp.int32)
        out = tide_attention(q, ak, av, table, lens, live, interpret=True)
        ref = tide_attention_ref(q, ak, av, table, lens, live)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


class TestOptimisticLookup:
    @pytest.mark.parametrize("N,window", [
        (1000, 128), (20000, 512), (50000, 2048), (300, 512),
    ])
    def test_vs_searchsorted(self, N, window):
        rng = np.random.default_rng(N)
        keys = np.unique(rng.integers(0, 2**32, N, dtype=np.uint32))
        queries = np.concatenate([
            rng.choice(keys, 64),
            rng.integers(0, 2**32, 64, dtype=np.uint32)]).astype(np.uint32)
        kj, qj = jnp.asarray(keys), jnp.asarray(queries)
        idx, found, iters = optimistic_lookup(qj, kj, window=window)
        ridx, rfound = optimistic_lookup_ref(qj, kj)
        resolved = np.asarray(idx) >= 0
        assert resolved.mean() > 0.99     # uniform keys: resolves in budget
        np.testing.assert_array_equal(np.asarray(found)[resolved],
                                      np.asarray(rfound)[resolved])
        hit = resolved & np.asarray(found)
        np.testing.assert_array_equal(np.asarray(idx)[hit],
                                      np.asarray(ridx)[hit])
        assert float(np.asarray(iters)[resolved].mean()) <= 3.0  # paper §4.2

    def test_ops_fallback_exact(self):
        rng = np.random.default_rng(7)
        keys = np.unique(rng.integers(0, 2**32, 5000, dtype=np.uint32))
        # adversarial: clustered keys break the uniformity assumption
        keys = np.unique(np.concatenate([keys, np.arange(
            2**31, 2**31 + 4096, dtype=np.uint32)]))
        queries = np.concatenate([
            keys[:64], np.arange(2**31, 2**31 + 64, dtype=np.uint32),
            rng.integers(0, 2**32, 64, dtype=np.uint32)]).astype(np.uint32)
        idx, found, unresolved, _ = lookup_indices_batch(
            queries, keys, window=128, max_iters=2)
        assert unresolved > 0                 # the host search ran
        ridx, rfound = optimistic_lookup_ref(jnp.asarray(queries),
                                             jnp.asarray(keys))
        np.testing.assert_array_equal(found, np.asarray(rfound))
        np.testing.assert_array_equal(idx[found], np.asarray(ridx)[found])

    @given(seed=st.integers(0, 2**31 - 1), n=st.integers(10, 3000),
           window=st.sampled_from([128, 512]))
    @SETTINGS
    def test_property(self, seed, n, window):
        rng = np.random.default_rng(seed)
        keys = np.unique(rng.integers(0, 2**32, n, dtype=np.uint32))
        queries = np.concatenate([
            rng.choice(keys, 16), rng.integers(0, 2**32, 16,
                                               dtype=np.uint32)
        ]).astype(np.uint32)
        pos = np.arange(len(keys), dtype=np.uint32) + 7
        idx, found, _, _ = lookup_indices_batch(queries, keys,
                                                window=window)
        got = np.where(found, pos[np.clip(idx, 0, len(keys) - 1)], 0)
        ridx, rfound = optimistic_lookup_ref(jnp.asarray(queries),
                                             jnp.asarray(keys))
        np.testing.assert_array_equal(found, np.asarray(rfound))
        exp = np.where(np.asarray(rfound),
                       pos[np.clip(np.asarray(ridx), 0, len(keys) - 1)], 0)
        np.testing.assert_array_equal(got, exp)


    def test_resolves_at_real_count_behind_padding(self):
        """5,000 real keys padded to 8,192: the kernel interpolates over the
        real count, so padding cannot skew its estimate."""
        rng = np.random.default_rng(5000)
        keys = np.unique(rng.integers(0, 2**32, 5000, dtype=np.uint32))
        n = len(keys)
        padded = np.concatenate(
            [keys, np.full(8192 - n, 0xFFFFFFFF, np.uint32)])
        queries = rng.choice(keys, 256)
        idx, found, _ = optimistic_lookup(
            jnp.asarray(queries), jnp.asarray(padded), n, window=512)
        idx, found = np.asarray(idx), np.asarray(found)
        resolved = idx >= 0
        assert resolved.mean() >= 0.99
        ridx, _ = optimistic_lookup_ref(jnp.asarray(queries),
                                        jnp.asarray(keys))
        np.testing.assert_array_equal(idx[resolved],
                                      np.asarray(ridx)[resolved])
        assert found[resolved].all()
        # The host entry pads the same column itself.
        _, bfound, unresolved, _ = lookup_indices_batch(queries, keys,
                                                        window=512)
        assert unresolved <= 0.01 * len(queries) and bfound.all()

    def test_segments_resolve_a_gapped_concatenation(self):
        """The store concatenates only the cells a batch touches.  With
        per-cell segments each query interpolates inside its own cell, so
        gaps between the touched cells cannot skew the estimate."""
        rng = np.random.default_rng(64)
        n_cells = 64
        keys = np.unique(rng.integers(0, 2**32, 60000, dtype=np.uint32))
        cell = (keys.astype(np.uint64) * n_cells) >> 32
        touched = np.r_[0:32, 63]              # a gap of 31 cells
        kept = keys[np.isin(cell, touched)]
        kcell = (kept.astype(np.uint64) * n_cells) >> 32
        starts = np.searchsorted(kcell, np.arange(n_cells))
        ends = np.searchsorted(kcell, np.arange(n_cells), side="right")
        queries = np.concatenate([rng.choice(kept, 240), rng.integers(
            0, 2**31, 16, dtype=np.uint32)])   # absent keys in cells 0-31
        qcell = (queries.astype(np.uint64) * n_cells) >> 32
        base = starts[qcell]
        frac = (queries.astype(np.uint64) * n_cells).astype(np.uint32)
        idx, found, unresolved, _ = lookup_indices_batch(
            queries, kept, segments=(base, ends[qcell] - base, frac),
            window=512)
        assert unresolved <= 0.01 * len(queries)
        ridx, rfound = optimistic_lookup_ref(jnp.asarray(queries),
                                             jnp.asarray(kept))
        np.testing.assert_array_equal(found, np.asarray(rfound))
        np.testing.assert_array_equal(idx, np.asarray(ridx))

    def test_multi_tile_window_matches_ref(self):
        """Windows of several tiles over a column that is not a whole
        number of tiles, with the window clamped at both ends."""
        rng = np.random.default_rng(3)
        keys = np.unique(rng.integers(0, 2**32, 9000, dtype=np.uint32))
        queries = np.concatenate([
            keys[:8], keys[-8:], rng.choice(keys, 100),
            rng.integers(0, 2**32, 12, dtype=np.uint32),
            np.array([0, 0xFFFFFFFF], np.uint32)]).astype(np.uint32)
        idx, found, iters = optimistic_lookup(
            jnp.asarray(queries), jnp.asarray(keys), window=3000)
        idx, found = np.asarray(idx), np.asarray(found)
        assert (idx >= 0).all() and int(np.asarray(iters).max()) <= 2
        ridx, rfound = optimistic_lookup_ref(jnp.asarray(queries),
                                             jnp.asarray(keys))
        np.testing.assert_array_equal(idx, np.asarray(ridx))
        np.testing.assert_array_equal(found, np.asarray(rfound))

    # Query counts on both sides of one chunk of 256 and of the chunk
    # buckets (1, 2, 4, 128 chunks); key counts on both sides of the
    # column's 4096-key floor.
    @pytest.mark.parametrize("q", [1, 255, 256, 257, 3 * 256 + 17,
                                   76 * 256 + 5])
    @pytest.mark.parametrize("n", [4095, 4097])
    @pytest.mark.parametrize("cells", [None, 4])
    def test_one_dispatch_over_every_chunk(self, q, n, cells):
        """However many queries, one call is one dispatch and answers
        exactly the real queries as the host's ``searchsorted`` does;
        with ``cells`` each query interpolates inside its own cell, as the
        store's concatenations do."""
        rng = np.random.default_rng(q * n)
        keys = np.sort(rng.choice(np.unique(rng.integers(
            0, 2**32, n + 64, dtype=np.uint32)), n, replace=False))
        queries = np.concatenate([
            rng.choice(keys, q // 2),
            rng.integers(0, 2**32, q - q // 2, dtype=np.uint32)])
        segments = None
        if cells:
            kcell = ((keys.astype(np.uint64) * cells) >> 32).astype(np.int64)
            starts = np.searchsorted(kcell, np.arange(cells))
            sizes = np.bincount(kcell, minlength=cells)
            qcell = (queries.astype(np.uint64) * cells) >> 32
            segments = (starts[qcell], sizes[qcell], (queries.astype(
                np.uint64) * cells).astype(np.uint32))
        idx, found, unresolved, copies = lookup_indices_batch(
            queries, keys, segments=segments, window=800)
        at = np.searchsorted(keys, queries, side="left")
        assert idx.shape == found.shape == (q,)
        np.testing.assert_array_equal(idx, at)
        np.testing.assert_array_equal(
            found, (at < n) & (keys[np.minimum(at, n - 1)] == queries))
        assert unresolved <= max(1, 0.01 * q)
        assert copies.dispatches == 1
        # the padded result comes back once: a power-of-two number of
        # 256-query chunks of i32 indices and 1-byte flags
        chunks = 1 << (-(-q // 256) - 1).bit_length()
        assert copies.d2h_bytes == chunks * 256 * (4 + 1)

    def test_chunks_past_the_real_queries_are_not_searched(self):
        """Of 1024 query slots only the first 300 are real: the chunks
        that hold none of them come back unresolved, with no window read,
        and the chunks before them as a call of the real queries alone."""
        rng = np.random.default_rng(11)
        keys = np.unique(rng.integers(0, 2**32, 6000, dtype=np.uint32))
        queries = jnp.asarray(rng.choice(keys, 1024))
        kj = jnp.asarray(keys)
        idx, found, iters = (np.asarray(a) for a in optimistic_lookup(
            queries, kj, n_queries=300, window=512))
        assert (idx[512:] == -1).all() and not found[512:].any()
        assert (iters[512:] == 0).all() and (iters[:512] >= 1).all()
        whole = (np.asarray(a) for a in optimistic_lookup(
            queries[:512], kj, window=512))
        for got, want in zip((idx, found, iters), whole):
            np.testing.assert_array_equal(got[:512], want)


class TestBloomCheck:
    @pytest.mark.parametrize("nwords,nadd,k", [(64, 20, 7), (256, 100, 7),
                                               (1024, 500, 5)])
    def test_vs_ref_no_false_negatives(self, nwords, nadd, k):
        rng = np.random.default_rng(nwords)
        bits = jnp.zeros((nwords,), jnp.uint32)
        h1a = jnp.asarray(rng.integers(0, 2**32, nadd, dtype=np.uint32))
        h2a = jnp.asarray(rng.integers(0, 2**32, nadd, dtype=np.uint32) | 1)
        bits = bloom_add_ref(h1a, h2a, bits, k=k)
        h1q = jnp.concatenate([h1a, jnp.asarray(
            rng.integers(0, 2**32, 200, dtype=np.uint32))])
        h2q = jnp.concatenate([h2a, jnp.asarray(
            rng.integers(0, 2**32, 200, dtype=np.uint32) | 1)])
        out = bloom_check(h1q, h2q, bits, k=k)
        ref = bloom_check_host(h1q, h2q, bits, k=k)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
        assert bool(jnp.all(out[:nadd]))          # no false negatives
        assert float(jnp.mean(out[nadd:])) < 0.35  # bounded false positives

    @given(seed=st.integers(0, 2**31 - 1))
    @SETTINGS
    def test_property(self, seed):
        rng = np.random.default_rng(seed)
        bits = jnp.zeros((128,), jnp.uint32)
        h1 = jnp.asarray(rng.integers(0, 2**32, 30, dtype=np.uint32))
        h2 = jnp.asarray(rng.integers(0, 2**32, 30, dtype=np.uint32) | 1)
        bits = bloom_add_ref(h1, h2, bits)
        out = bloom_check(h1, h2, bits)
        assert bool(jnp.all(out))


class TestBloomCheckRagged:
    def _cells(self, seed, nwords_list, nadd_list, k=7):
        """Per-cell bitsets built via the flat ref; returns packed buffer
        plus per-cell (h1a, h2a) of added hashes."""
        rng = np.random.default_rng(seed)
        cells = []
        for nwords, nadd in zip(nwords_list, nadd_list):
            h1a = rng.integers(0, 2**32, nadd, dtype=np.uint32)
            h2a = rng.integers(0, 2**32, nadd, dtype=np.uint32) | 1
            bits = bloom_add_ref(jnp.asarray(h1a), jnp.asarray(h2a),
                                 jnp.zeros((nwords,), jnp.uint32), k=k)
            cells.append((np.asarray(bits), h1a, h2a, nwords * 32))
        return cells

    def _ragged_inputs(self, cells, n_miss, seed):
        rng = np.random.default_rng(seed + 1)
        h1, h2, off, nb = [], [], [], []
        base = 0
        bounds = []
        for bits, h1a, h2a, nbits in cells:
            h1m = rng.integers(0, 2**32, n_miss, dtype=np.uint32)
            h2m = rng.integers(0, 2**32, n_miss, dtype=np.uint32) | 1
            h1.extend([h1a, h1m]); h2.extend([h2a, h2m])
            q = len(h1a) + n_miss
            off.append(np.full(q, base, np.int32))
            nb.append(np.full(q, nbits, np.uint32))
            bounds.append((len(h1a), n_miss))
            base += len(bits)
        packed = np.concatenate([c[0] for c in cells])
        return (np.concatenate(h1), np.concatenate(h2),
                np.concatenate(off), np.concatenate(nb), packed, bounds)

    @pytest.mark.parametrize("nwords_list,nadd_list", [
        ([64, 256, 16], [20, 100, 4]),
        ([2, 128, 2, 1024], [0, 50, 1, 400]),     # empty + tiny cells
        ([512], [200]),                           # single cell
    ])
    def test_vs_ref_and_flat_percell(self, nwords_list, nadd_list):
        """The fused probe equals the numpy probe AND the per-cell flat
        probe sliced back out — fusion introduces no false negatives."""
        cells = self._cells(7, nwords_list, nadd_list)
        h1, h2, off, nb, packed, bounds = self._ragged_inputs(cells, 25, 7)
        out = bloom_check_ragged(jnp.asarray(h1), jnp.asarray(h2),
                                 jnp.asarray(off), jnp.asarray(nb),
                                 jnp.asarray(packed))
        ref = _probe_host(h1, h2, off, nb, packed, 7)
        np.testing.assert_array_equal(np.asarray(out), ref)
        pos = 0
        for (bits, h1a, h2a, nbits), (nadd, n_miss) in zip(cells, bounds):
            q = nadd + n_miss
            flat = bloom_check(jnp.asarray(h1[pos:pos + q]),
                               jnp.asarray(h2[pos:pos + q]),
                               jnp.asarray(bits), nbits=nbits)
            np.testing.assert_array_equal(np.asarray(out[pos:pos + q]),
                                          np.asarray(flat))
            assert bool(np.all(np.asarray(out[pos:pos + nadd])))
            pos += q

    @given(seed=st.integers(0, 2**31 - 1),
           shapes=st.lists(st.sampled_from([2, 8, 64, 256]),
                           min_size=1, max_size=4))
    @SETTINGS
    def test_property_matches_percell(self, seed, shapes):
        rng = np.random.default_rng(seed)
        nadds = [int(rng.integers(0, nw * 3)) for nw in shapes]
        cells = self._cells(seed, shapes, nadds)
        h1, h2, off, nb, packed, bounds = self._ragged_inputs(cells, 9, seed)
        out = np.asarray(bloom_check_ragged(
            jnp.asarray(h1), jnp.asarray(h2), jnp.asarray(off),
            jnp.asarray(nb), jnp.asarray(packed)))
        pos = 0
        for (bits, _, _, nbits), (nadd, n_miss) in zip(cells, bounds):
            q = nadd + n_miss
            flat = bloom_check_host(h1[pos:pos + q], h2[pos:pos + q], bits,
                                    nbits=nbits)
            np.testing.assert_array_equal(out[pos:pos + q], flat)
            pos += q


    def test_blocked_grid_matches_ref(self):
        """A batch that is not a power of two over cells whose moduli are
        not powers of two, packed into a buffer of odd size."""
        rng = np.random.default_rng(2500)
        sizes = [640, 2048, 8, 1500]           # words per cell
        bases = np.concatenate([[0], np.cumsum(sizes[:-1])])
        packed = rng.integers(0, 2**32, sum(sizes), dtype=np.uint32)
        packed |= rng.integers(0, 2**32, sum(sizes), dtype=np.uint32)
        q = 2500
        cell = np.sort(rng.integers(0, len(sizes), q))
        h1 = rng.integers(0, 2**32, q, dtype=np.uint32)
        h2 = rng.integers(0, 2**32, q, dtype=np.uint32) | 1
        off = bases[cell].astype(np.int32)
        nb = (np.asarray(sizes)[cell] * 32 - cell).astype(np.uint32)
        args = tuple(map(jnp.asarray, (h1, h2, off, nb, packed)))
        out = np.asarray(bloom_check_ragged(*args))
        ref = _probe_host(h1, h2, off, nb, packed, 7)
        np.testing.assert_array_equal(out, ref)
        assert 0 < out.mean() < 1


class TestSsdScan:
    def _inputs(self, key, b, l, h, p, n):
        ks = jax.random.split(key, 5)
        x = jax.random.normal(ks[0], (b, l, h, p), jnp.float32)
        dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
        A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
        Bm = jax.random.normal(ks[3], (b, l, n)) * 0.5
        Cm = jax.random.normal(ks[4], (b, l, n)) * 0.5
        return x, dt, A, Bm, Cm

    @pytest.mark.parametrize("b,l,h,p,n,c", [
        (2, 64, 8, 16, 32, 16),
        (1, 128, 4, 64, 128, 32),     # production-like head/state dims
        (3, 48, 8, 16, 16, 16),
        (2, 40, 4, 16, 32, 16),       # padding path via ops wrapper
    ])
    def test_vs_ref(self, b, l, h, p, n, c):
        from repro.kernels.ssd_scan.ops import ssd
        from repro.kernels.ssd_scan.ref import ssd_scan_ref
        x, dt, A, Bm, Cm = self._inputs(jax.random.PRNGKey(l), b, l, h, p, n)
        y, st = ssd(x, dt, A, Bm, Cm, chunk=c)
        yr, sr = ssd_scan_ref(x, dt, A, Bm, Cm, c)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=3e-4, atol=3e-4)
        np.testing.assert_allclose(np.asarray(st), np.asarray(sr),
                                   rtol=3e-4, atol=3e-4)

    @given(seed=st.integers(0, 2**31 - 1))
    @SETTINGS
    def test_property(self, seed):
        from repro.kernels.ssd_scan.kernel import ssd_scan_pallas
        from repro.kernels.ssd_scan.ref import ssd_scan_ref
        x, dt, A, Bm, Cm = self._inputs(jax.random.PRNGKey(seed),
                                        2, 32, 4, 8, 16)
        y, stt = ssd_scan_pallas(x, dt, A, Bm, Cm, chunk=8, interpret=True)
        yr, sr = ssd_scan_ref(x, dt, A, Bm, Cm, 8)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=3e-4, atol=3e-4)
        np.testing.assert_allclose(np.asarray(stt), np.asarray(sr),
                                   rtol=3e-4, atol=3e-4)
