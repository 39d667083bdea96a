"""Owned-rows pallas scatter + fused query kernels (ISSUE 10).

Conformance contracts under test:

* partitioned ``backend="pallas"`` vs the `ref_python.gee_numpy_owned`
  oracle across random RowPartitions x K x tile geometries, including
  tail tiles and empty partition slices — and bit-identical across
  runs;
* the fused normalize+cosine+top-k kernel is ``np.array_equal`` (NOT
  tie-tolerant) to the jitted blocked scan for every tested shard
  count, per-slice and after the cross-shard merge;
* the fused delta-apply+renormalize kernel matches partial_fit +
  normalize_rows;
* ``interpret="auto"`` resolution is recorded in plan metadata and the
  embed info dict.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.ref_python import gee_numpy_owned
from repro.encoder import Embedder, EncoderConfig
from repro.encoder.plan import effective_weights, owned_contributions
from repro.graph.edges import Graph, make_labels
from repro.graph.generators import erdos_renyi, powerlaw
from repro.graph.partition import RowPartition
from repro.kernels.gee_scatter import resolve_interpret
from repro.serving import queries as Q
from repro.serving.engine import GraphStore, ServingEngine


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def _graph_labels(n=220, s=1800, K=5, seed=3):
    g = erdos_renyi(n, s, seed=seed, weighted=True)
    Y = make_labels(n, K, 0.3, np.random.default_rng(seed))
    return g, Y


def _owned_oracle(g, Y, K, lo, hi):
    from repro.core.gee import make_w
    w_eff = effective_weights(g, EncoderConfig(K=K))
    rows, src, w = owned_contributions(g, w_eff, lo, hi)
    Wv = np.asarray(make_w(jnp.asarray(Y), K))
    return gee_numpy_owned(rows, src, w, np.asarray(Y), Wv, K, hi - lo)


class TestOwnedRowsPallas:
    """Partitioned pallas plans pack owned contributions over local
    rows [0, hi - lo) and accumulate O(n/p), not O(n)."""

    @pytest.mark.parametrize("K", [3, 8])
    @pytest.mark.parametrize("tile_n,edge_block", [(64, 128), (32, 64)])
    @pytest.mark.parametrize("parts", [2, 3])
    def test_matches_owned_oracle_across_partitions(self, K, tile_n,
                                                    edge_block, parts):
        g, Y = _graph_labels(K=K, seed=K + parts)
        for lo, hi in RowPartition(g.n, parts).slices():
            emb = Embedder(EncoderConfig(K=K, tile_n=tile_n,
                                         edge_block=edge_block,
                                         row_partition=(lo, hi)),
                           backend="pallas", plan_cache=None).fit(g, Y)
            assert emb.Z_.shape == (hi - lo, K)       # O(n/p), not O(n)
            np.testing.assert_allclose(
                np.asarray(emb.Z_), _owned_oracle(g, Y, K, lo, hi),
                atol=1e-5)

    def test_tail_tile_partition(self):
        """n_local deliberately NOT a tile multiple: the kernel's tail
        tile must accumulate exactly and slice back to (hi - lo, K)."""
        g, Y = _graph_labels(n=200, s=1500)
        lo, hi = 37, 150                               # n_local = 113
        emb = Embedder(EncoderConfig(K=5, tile_n=64, edge_block=128,
                                     row_partition=(lo, hi)),
                       backend="pallas", plan_cache=None).fit(g, Y)
        assert emb.Z_.shape == (113, 5)
        np.testing.assert_allclose(np.asarray(emb.Z_),
                                   _owned_oracle(g, Y, 5, lo, hi),
                                   atol=1e-5)

    def test_empty_partition_slice(self):
        """A slice no edge lands in packs an empty contribution set and
        embeds to zeros (not an error, not garbage)."""
        rng = np.random.default_rng(5)
        u = rng.integers(0, 10, 80).astype(np.int32)
        v = rng.integers(0, 10, 80).astype(np.int32)
        g = Graph(u, v, np.ones(80, np.float32), 100)
        Y = make_labels(100, 4, 0.5, rng)
        emb = Embedder(EncoderConfig(K=4, tile_n=32, edge_block=64,
                                     row_partition=(50, 60)),
                       backend="pallas", plan_cache=None).fit(g, Y)
        assert emb.Z_.shape == (10, 4)
        assert np.all(np.asarray(emb.Z_) == 0)

    def test_skewed_destinations_partitioned(self):
        """Power-law graphs stress per-tile bucket padding inside a
        partition slice too."""
        g = powerlaw(300, 5000, seed=9)
        Y = make_labels(300, 8, 0.25, np.random.default_rng(9))
        emb = Embedder(EncoderConfig(K=8, tile_n=64, edge_block=128,
                                     row_partition=(0, 120)),
                       backend="pallas", plan_cache=None).fit(g, Y)
        np.testing.assert_allclose(np.asarray(emb.Z_),
                                   _owned_oracle(g, Y, 8, 0, 120),
                                   atol=1e-5)

    def test_bit_identical_across_runs(self):
        g, Y = _graph_labels()
        cfg = EncoderConfig(K=5, tile_n=64, edge_block=128,
                            row_partition=(40, 173))
        Z1 = Embedder(cfg, backend="pallas", plan_cache=None).fit(g, Y).Z_
        Z2 = Embedder(cfg, backend="pallas", plan_cache=None).fit(g, Y).Z_
        assert np.array_equal(np.asarray(Z1), np.asarray(Z2))

    def test_packed_blocks_are_the_tier2_artifact(self, tmp_path):
        """A second partitioned pallas Embedder hits the persisted
        packed blocks; a different partition misses (keyed on it)."""
        g, Y = _graph_labels()
        cfg = EncoderConfig(K=5, tile_n=64, edge_block=128,
                            row_partition=(0, 110))
        a = Embedder(cfg, backend="pallas", plan_cache=tmp_path)
        a.fit(g, Y)
        assert a.plan_stats["disk_stores"] == 1
        b = Embedder(cfg, backend="pallas", plan_cache=tmp_path)
        b.fit(Graph(g.u.copy(), g.v.copy(), g.w.copy(), g.n), Y)
        assert b.plan_stats == {"built": 0, "hits": 0,
                                "disk_hits": 1, "disk_stores": 0}
        assert np.array_equal(np.asarray(a.Z_), np.asarray(b.Z_))
        c = Embedder(EncoderConfig(K=5, tile_n=64, edge_block=128,
                                   row_partition=(110, 220)),
                     backend="pallas", plan_cache=tmp_path)
        c.fit(g, Y)
        assert c.plan_stats["disk_hits"] == 0
        assert c.plan_stats["built"] == 1


class TestFusedTopK:
    """The fused kernel must be np.array_equal — not tie-tolerant — to
    the jitted blocked scan, per shard slice and after the merge."""

    K, M, NQ, TOPK = 6, 160, 12, 9

    def _fixture(self, rng, duplicates=True):
        base = rng.normal(size=(self.M // 4, self.K)).astype(np.float32)
        # duplicate-heavy rows maximize score ties: the id-order tie
        # contract is what the equality below actually exercises
        Z = np.repeat(base, 4, axis=0) if duplicates else \
            rng.normal(size=(self.M, self.K)).astype(np.float32)
        Zn = Q.normalize_rows(jnp.asarray(Z))
        qnodes = rng.integers(0, self.M, self.NQ).astype(np.int32)
        q = Zn[jnp.asarray(qnodes)]
        return Z, Zn, q, qnodes

    @pytest.mark.parametrize("p", [1, 2, 4])
    @pytest.mark.parametrize("block_rows", [16, 64, 1 << 14])
    def test_bitwise_equal_per_slice_and_merged(self, p, block_rows,
                                                rng):
        Z, Zn, q, qnodes = self._fixture(rng)
        bounds = np.linspace(0, self.M, p + 1).astype(int)
        ref_parts, fus_parts = [], []
        for lo, hi in zip(bounds[:-1], bounds[1:]):
            ref = Q.topk_cosine_q(Zn[lo:hi], q, qnodes, k=self.TOPK,
                                  block_rows=block_rows, row_offset=lo)
            fus = Q.topk_cosine_fused(Zn[lo:hi], q, qnodes, k=self.TOPK,
                                      block_rows=block_rows,
                                      row_offset=lo)
            assert np.array_equal(ref[0], fus[0])
            assert np.array_equal(ref[1], fus[1])
            ref_parts.append(ref)
            fus_parts.append(fus)
        mr = Q.merge_topk([r[0] for r in ref_parts],
                          [r[1] for r in ref_parts], k=self.TOPK)
        mf = Q.merge_topk([f[0] for f in fus_parts],
                          [f[1] for f in fus_parts], k=self.TOPK)
        assert np.array_equal(mr[0], mf[0])
        assert np.array_equal(mr[1], mf[1])

    @pytest.mark.parametrize("normalize", [False, True])
    def test_kernel_blocking_matches_scan(self, normalize, rng,
                                          monkeypatch,
                                          assert_topk_equivalent):
        """The kernel's own row and query blocks (bounds shrunk so this
        fixture spans several of each) keep the scan's answer: a
        different matmul shape may move a score by an ulp on CPU, so
        the comparison is tie-tolerant at 1e-6."""
        from repro.kernels import query_fused as QF
        monkeypatch.setattr(QF, "MAX_BLOCK_ROWS", 16)
        monkeypatch.setattr(QF, "QUERY_BLOCK", 8)
        QF.topk_fused.clear_cache()
        try:
            Z, Zn, q, qnodes = self._fixture(rng)
            ref = Q.topk_cosine_q(Zn, q, qnodes, k=self.TOPK,
                                  block_rows=64, row_offset=7)
            if normalize:
                fi, fv, Zn2 = Q.topk_cosine_fused_norm(
                    jnp.asarray(Z), q, qnodes, k=self.TOPK,
                    block_rows=64, row_offset=7)
                assert np.array_equal(np.asarray(Zn), np.asarray(Zn2))
            else:
                fi, fv = Q.topk_cosine_fused(Zn, q, qnodes, k=self.TOPK,
                                             block_rows=64, row_offset=7)
            assert_topk_equivalent(fi, fv, ref[0], ref[1], atol=1e-6)
        finally:
            QF.topk_fused.clear_cache()

    def test_norm_mode_matches_separate_passes(self, rng):
        """Fused normalize+scan == normalize_rows -> blocked scan, and
        the emitted Zn is bit-identical to normalize_rows."""
        Z, Zn, q, qnodes = self._fixture(rng)
        ref = Q.topk_cosine_q(Zn, q, qnodes, k=self.TOPK, block_rows=32)
        fi, fv, Zn2 = Q.topk_cosine_fused_norm(
            jnp.asarray(Z), q, qnodes, k=self.TOPK, block_rows=32)
        assert np.array_equal(ref[0], fi)
        assert np.array_equal(ref[1], fv)
        assert np.array_equal(np.asarray(Zn), np.asarray(Zn2))

    def test_k_exceeds_candidates_clamps(self, rng):
        Z, Zn, q, qnodes = self._fixture(rng)
        few = Zn[:3]
        ref = Q.topk_cosine_q(few, q, qnodes, k=8, block_rows=16)
        fus = Q.topk_cosine_fused(few, q, qnodes, k=8, block_rows=16)
        assert np.array_equal(ref[0], fus[0])
        assert np.array_equal(ref[1], fus[1])
        assert (fus[0] == -1).any()                  # clamped tail

    def test_exclude_self_off(self, rng):
        Z, Zn, q, qnodes = self._fixture(rng)
        ref = Q.topk_cosine_q(Zn, q, qnodes, k=self.TOPK,
                              block_rows=64, exclude_self=False)
        fus = Q.topk_cosine_fused(Zn, q, qnodes, k=self.TOPK,
                                  block_rows=64, exclude_self=False)
        assert np.array_equal(ref[0], fus[0])
        assert np.array_equal(ref[1], fus[1])


class TestFusedDelta:
    """partial_fit_norm: one pass == partial_fit + normalize_rows."""

    def _fitted(self, **cfg_kw):
        g, Y = _graph_labels()
        cfg = EncoderConfig(K=5, tile_n=64, edge_block=128, **cfg_kw)
        return (Embedder(cfg, backend="pallas", plan_cache=None)
                .fit(g, Y), g)

    @pytest.mark.parametrize("rp", [None, (40, 173)])
    def test_matches_partial_fit_then_normalize(self, rp, rng):
        kw = {} if rp is None else {"row_partition": rp}
        e1, g = self._fitted(**kw)
        e2, _ = self._fitted(**kw)
        d = Graph(rng.integers(0, g.n, 40).astype(np.int32),
                  rng.integers(0, g.n, 40).astype(np.int32),
                  rng.random(40, dtype=np.float32) + 0.5, g.n)
        Zn = e1.partial_fit_norm(d)
        e2.partial_fit(d)
        np.testing.assert_allclose(np.asarray(e1.Z_), np.asarray(e2.Z_),
                                   atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(Zn), np.asarray(Q.normalize_rows(e1.Z_)),
            atol=1e-6)

    def test_deterministic_and_sign_roundtrip(self, rng):
        e1, g = self._fitted(row_partition=(40, 173))
        e2, _ = self._fitted(row_partition=(40, 173))
        Z0 = np.asarray(e1.Z_).copy()
        d = Graph(rng.integers(0, g.n, 30).astype(np.int32),
                  rng.integers(0, g.n, 30).astype(np.int32),
                  rng.random(30, dtype=np.float32) + 0.5, g.n)
        Zn1 = e1.partial_fit_norm(d)
        Zn2 = e2.partial_fit_norm(d)
        assert np.array_equal(np.asarray(e1.Z_), np.asarray(e2.Z_))
        assert np.array_equal(np.asarray(Zn1), np.asarray(Zn2))
        e1.partial_fit_norm(d, sign=-1.0)            # exact inverse
        np.testing.assert_allclose(np.asarray(e1.Z_), Z0, atol=1e-4)

    def test_guards_mirror_partial_fit(self, rng):
        g, Y = _graph_labels()
        emb = Embedder(EncoderConfig(K=5, tile_n=64, edge_block=128),
                       backend="pallas", plan_cache=None)
        from repro.encoder.embedder import NotFittedError
        d = Graph(np.array([0], np.int32), np.array([1], np.int32),
                  np.ones(1, np.float32), g.n)
        with pytest.raises(NotFittedError):
            emb.partial_fit_norm(d)
        emb.fit(g, Y)
        emb.partial_fit_norm(d)
        with pytest.raises(RuntimeError, match="partial_fit"):
            emb.refit(Y)                 # deltas pending, like partial_fit


class TestPallasServing:
    """End-to-end: a pallas-backed engine serves through the fused
    kernels.  Cross-BACKEND comparisons are allclose (streaming and
    pallas accumulate Z in different orders); the fused-vs-blocked
    bitwise contract on a FIXED Zn is covered in TestFusedTopK, and
    here the cold (normalize-in-kernel) and warm (cached Zn) fused
    paths must answer bit-identically."""

    def _store(self, seed=4):
        g = erdos_renyi(240, 2400, seed=seed, weighted=True)
        Y = make_labels(240, 6, 0.4, np.random.default_rng(seed))
        return GraphStore(g, Y, 6)

    @pytest.mark.parametrize("p", [1, 2, 4])
    def test_engine_matches_streaming(self, p, rng):
        ref = ServingEngine(self._store(), num_shards=p)
        pal = ServingEngine(self._store(), num_shards=p,
                            backend="pallas")
        np.testing.assert_allclose(np.asarray(pal.Z), np.asarray(ref.Z),
                                   atol=1e-5)
        nodes = rng.integers(0, 240, 32).astype(np.int32)
        a = ref.query_topk(nodes, k=10)
        b = pal.query_topk(nodes, k=10)   # cold: fused normalize+scan
        c = pal.query_topk(nodes, k=10)   # warm: fused scan of cached Zn
        assert np.array_equal(b[0], c[0])
        assert np.array_equal(b[1], c[1])
        np.testing.assert_allclose(b[1], a[1], atol=1e-5)

    @pytest.mark.parametrize("p", [2, 4])
    def test_engine_after_delta(self, p, rng):
        ref = ServingEngine(self._store(), num_shards=p)
        pal = ServingEngine(self._store(), num_shards=p,
                            backend="pallas")
        u = rng.integers(0, 240, 100).astype(np.int32)
        v = rng.integers(0, 240, 100).astype(np.int32)
        w = rng.random(100, dtype=np.float32) + 0.5
        ref.apply_edge_delta(u, v, w)
        pal.apply_edge_delta(u, v, w)    # fused apply+renorm path
        np.testing.assert_allclose(np.asarray(pal.Z), np.asarray(ref.Z),
                                   atol=1e-5)
        nodes = rng.integers(0, 240, 24).astype(np.int32)
        a = ref.query_topk(nodes, k=8)
        b = pal.query_topk(nodes, k=8)
        np.testing.assert_allclose(b[1], a[1], atol=1e-5)
        # determinism of the fused path itself
        c = pal.query_topk(nodes, k=8)
        assert np.array_equal(b[0], c[0])
        assert np.array_equal(b[1], c[1])


class TestInterpretResolution:
    def test_resolve_semantics(self):
        assert resolve_interpret(True) is True
        assert resolve_interpret(False) is False
        expect = jax.default_backend() not in ("tpu", "gpu")
        assert resolve_interpret("auto") is expect
        assert resolve_interpret(None) is expect

    def test_recorded_in_plan_and_info(self):
        g, Y = _graph_labels()
        emb = Embedder(EncoderConfig(K=5, tile_n=64, edge_block=128),
                       backend="pallas", plan_cache=None).fit(g, Y)
        expect = jax.default_backend() not in ("tpu", "gpu")
        assert emb._plan.data["interpret"] is expect
        assert emb.last_info_["interpret"] is expect
        # never persisted: the host half holds only the packed blocks
        assert "interpret" not in emb._plan.host

    def test_config_rejects_junk(self):
        with pytest.raises(ValueError, match="interpret"):
            EncoderConfig(K=3, interpret="yes")
