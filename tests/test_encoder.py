"""Cross-backend conformance: every registered backend computes the
same Z through the unified `repro.encoder.Embedder` front door, to
float tolerance — plus the Embedder contract itself: plan caching,
owned projection weights, exact partial_fit, refinement.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.gee import make_w
from repro.core.ref_python import gee_numpy
from repro.encoder import (Embedder, EncoderConfig, NotFittedError,
                           get_backend, list_backends, register_backend)
from repro.graph.edges import Graph, make_labels
from repro.graph.generators import erdos_renyi, sbm

ALL_BACKENDS = list_backends()
# small kernel geometry so pallas exercises multi-tile packing; small
# chunks so streaming exercises multi-chunk accumulation
CFG = dict(tile_n=64, edge_block=128, chunk_size=256)


def _oracle(g, Y, K, laplacian=False):
    w = g.w
    if laplacian:
        deg = g.degrees()
        sc = 1.0 / np.sqrt(np.maximum(deg, 1.0))
        w = (w * sc[g.u] * sc[g.v]).astype(np.float32)
    return gee_numpy(g.u, g.v, w, Y, K, g.n)


def _cases():
    """Weighted/directed/self-loop/partially-labeled graph zoo."""
    rng = np.random.default_rng(0)
    cases = {}
    g = erdos_renyi(130, 700, seed=2, weighted=True)     # weighted digraph
    cases["weighted_directed"] = (g, make_labels(130, 5, 0.4, rng))
    loops = Graph(np.arange(40, dtype=np.int32),
                  np.arange(40, dtype=np.int32),
                  rng.random(40, dtype=np.float32) + 0.5, 40)
    mixed = erdos_renyi(40, 160, seed=3, weighted=True)
    g2 = Graph(np.concatenate([mixed.u, loops.u]),
               np.concatenate([mixed.v, loops.v]),
               np.concatenate([mixed.w, loops.w]), 40)   # self-loops
    cases["self_loops"] = (g2, make_labels(40, 4, 0.5, rng))
    g3 = erdos_renyi(90, 400, seed=4, weighted=True)
    Y3 = np.full(90, -1, np.int32)                       # 3 labeled nodes
    Y3[[0, 7, 31]] = [0, 1, 2]
    cases["sparsely_labeled"] = (g3, Y3)
    Y4 = make_labels(130, 5, 0.4, rng)              # no node carries 2
    cases["empty_class"] = (g, np.where(Y4 == 2, -1, Y4).astype(np.int32))
    cases["all_unlabeled"] = (g3, np.full(90, -1, np.int32))
    return cases


class TestConformance:
    @pytest.mark.parametrize("backend", ALL_BACKENDS)
    @pytest.mark.parametrize("case", sorted(_cases()))
    def test_all_backends_match_oracle(self, backend, case):
        g, Y = _cases()[case]
        K = int(Y.max()) + 1 if Y.max() >= 0 else 3
        emb = Embedder(EncoderConfig(K=K, **CFG), backend=backend)
        emb.fit(g, Y)
        Z = emb.transform()
        np.testing.assert_allclose(Z, _oracle(g, Y, K), atol=1e-5)
        # a class no node carries weighs 0, not 1/0: its column is 0
        absent = np.setdiff1d(np.arange(K), Y)
        assert np.isfinite(Z).all() and np.all(Z[:, absent] == 0)

    @pytest.mark.parametrize("backend", ALL_BACKENDS)
    def test_laplacian_conformance(self, backend):
        g, Y = _cases()["weighted_directed"]
        emb = Embedder(EncoderConfig(K=5, laplacian=True, **CFG),
                       backend=backend)
        emb.fit(g, Y)
        np.testing.assert_allclose(
            emb.transform(), _oracle(g, Y, 5, laplacian=True), atol=1e-4)

    @pytest.mark.parametrize("backend", ALL_BACKENDS)
    def test_empty_graph(self, backend):
        g = Graph(np.zeros(0, np.int32), np.zeros(0, np.int32),
                  np.zeros(0, np.float32), 16)
        Y = make_labels(16, 3, 0.5, np.random.default_rng(1))
        emb = Embedder(EncoderConfig(K=3, **CFG), backend=backend)
        emb.fit(g, Y)
        assert emb.transform().shape == (16, 3)
        assert np.all(emb.transform() == 0)


class TestPartialFit:
    def test_delta_then_delete_roundtrip(self):
        g, Y = _cases()["weighted_directed"]
        emb = Embedder(EncoderConfig(K=5), backend="xla").fit(g, Y)
        Z0 = emb.transform()
        rng = np.random.default_rng(9)
        d = Graph(rng.integers(0, g.n, 60).astype(np.int32),
                  rng.integers(0, g.n, 60).astype(np.int32),
                  rng.random(60, dtype=np.float32) + 0.5, g.n)
        emb.partial_fit(d)
        # live multiset = g ++ d
        both = Graph(np.concatenate([g.u, d.u]), np.concatenate([g.v, d.v]),
                     np.concatenate([g.w, d.w]), g.n)
        np.testing.assert_allclose(emb.transform(), _oracle(both, Y, 5),
                                   atol=1e-4)
        emb.partial_fit(d, sign=-1.0)
        np.testing.assert_allclose(emb.transform(), Z0, atol=1e-4)

    def test_empty_delta_is_noop(self):
        g, Y = _cases()["weighted_directed"]
        emb = Embedder(EncoderConfig(K=5), backend="xla").fit(g, Y)
        Z0 = emb.transform()
        emb.partial_fit(Graph(np.zeros(0, np.int32), np.zeros(0, np.int32),
                              np.zeros(0, np.float32), g.n))
        np.testing.assert_array_equal(emb.transform(), Z0)

    def test_owned_weights_ignore_caller_label_drift(self):
        """The old `gee_apply_delta(Wv=...)` footgun: deltas must use the
        weights Z was BUILT with, even if the caller's labels moved."""
        g, Y = _cases()["weighted_directed"]
        emb = Embedder(EncoderConfig(K=5), backend="xla").fit(g, Y)
        Y_drifted = Y.copy()
        Y_drifted[:20] = (Y_drifted[:20] + 1) % 5      # caller-side churn
        d = Graph(np.array([1, 2], np.int32), np.array([3, 4], np.int32),
                  np.ones(2, np.float32), g.n)
        emb.partial_fit(d)                  # uses owned (labels_, Wv_)
        both = Graph(np.concatenate([g.u, d.u]), np.concatenate([g.v, d.v]),
                     np.concatenate([g.w, d.w]), g.n)
        np.testing.assert_allclose(emb.transform(), _oracle(both, Y, 5),
                                   atol=1e-4)

    def test_laplacian_partial_fit_rejected(self):
        g, Y = _cases()["weighted_directed"]
        emb = Embedder(EncoderConfig(K=5, laplacian=True),
                       backend="xla").fit(g, Y)
        with pytest.raises(ValueError, match="laplacian"):
            emb.partial_fit(Graph(np.array([0], np.int32),
                                  np.array([1], np.int32),
                                  np.ones(1, np.float32), g.n))

    def test_refit_after_partial_fit_rejected(self):
        """refit re-embeds the plan's ORIGINAL multiset; after deltas
        that would silently discard them — it must refuse."""
        g, Y = _cases()["weighted_directed"]
        emb = Embedder(EncoderConfig(K=5), backend="xla").fit(g, Y)
        emb.partial_fit(Graph(np.array([0], np.int32),
                              np.array([1], np.int32),
                              np.ones(1, np.float32), g.n))
        with pytest.raises(RuntimeError, match="discard"):
            emb.refit(Y)
        with pytest.raises(RuntimeError, match="discard"):
            emb.refine()
        # a fresh fit on the live graph clears the guard
        live = Graph(np.concatenate([g.u, [0]]).astype(np.int32),
                     np.concatenate([g.v, [1]]).astype(np.int32),
                     np.concatenate([g.w, [1.0]]).astype(np.float32), g.n)
        emb.fit(live, Y)
        emb.refit(Y)                       # allowed again
        np.testing.assert_allclose(emb.transform(), _oracle(live, Y, 5),
                                   atol=1e-5)

    def test_wrong_n_rejected(self):
        g, Y = _cases()["weighted_directed"]
        emb = Embedder(EncoderConfig(K=5), backend="xla").fit(g, Y)
        with pytest.raises(ValueError, match="n="):
            emb.partial_fit(Graph(np.array([0], np.int32),
                                  np.array([1], np.int32),
                                  np.ones(1, np.float32), g.n + 5))


class TestPlanCache:
    @pytest.mark.parametrize("backend",
                             ["xla", "pallas",
                              "distributed:reduce_scatter"])
    def test_same_arrays_hit_cache(self, backend):
        g, Y = _cases()["weighted_directed"]
        emb = Embedder(EncoderConfig(K=5, **CFG), backend=backend)
        emb.fit(g, Y)
        emb.fit(g, Y)
        emb.refit(Y)
        assert emb.plan_stats == {"built": 1, "hits": 2,
                                  "disk_hits": 0, "disk_stores": 0}

    def test_new_arrays_rebuild_plan(self):
        g, Y = _cases()["weighted_directed"]
        emb = Embedder(EncoderConfig(K=5), backend="xla").fit(g, Y)
        g2 = Graph(g.u.copy(), g.v.copy(), g.w.copy(), g.n)
        emb.fit(g2, Y)                    # same content, new arrays
        assert emb.plan_stats["built"] == 2

    def test_plan_swap_invalidates_fitted_state(self):
        """plan() on a different graph must not leave refit/transform
        serving the old fit against the new plan."""
        g, Y = _cases()["weighted_directed"]
        emb = Embedder(EncoderConfig(K=5), backend="xla").fit(g, Y)
        g2 = Graph(g.u.copy(), g.v.copy(), g.w.copy(), g.n)
        emb.plan(g2)
        with pytest.raises(NotFittedError):
            emb.refit(Y)
        with pytest.raises(NotFittedError):
            emb.transform()
        emb.fit(g2, Y)                     # fitting again recovers
        np.testing.assert_allclose(emb.transform(), _oracle(g, Y, 5),
                                   atol=1e-5)

    def test_refit_with_new_labels_skips_packing(self):
        """The load-bearing property: label churn (refinement rounds,
        serving epochs) must not re-run host-side packing."""
        g, Y = _cases()["weighted_directed"]
        emb = Embedder(EncoderConfig(K=5, **CFG), backend="pallas")
        emb.fit(g, Y)
        Y2 = make_labels(g.n, 5, 0.7, np.random.default_rng(42))
        emb.refit(Y2)
        assert emb.plan_stats == {"built": 1, "hits": 1,
                                  "disk_hits": 0, "disk_stores": 0}
        np.testing.assert_allclose(emb.transform(), _oracle(g, Y2, 5),
                                   atol=1e-5)


class TestPersistentPlanCache:
    """Tier 2 (content-addressed, on-disk) of the plan cache; the
    cross-PROCESS acceptance tests live in tests/test_plan_cache.py —
    here we prove in-process that disk-loaded plans compute the same Z
    for every persistable backend."""

    @pytest.mark.parametrize("backend", ALL_BACKENDS)
    @pytest.mark.parametrize("laplacian", [False, True])
    def test_z_agreement_from_disk_plans(self, backend, laplacian,
                                         tmp_path):
        g, Y = _cases()["weighted_directed"]
        cfg = EncoderConfig(K=5, laplacian=laplacian, **CFG)
        warm = Embedder(cfg, backend=backend, plan_cache=tmp_path)
        warm.fit(g, Y)
        assert warm.plan_stats["disk_stores"] == 1
        # a fresh Embedder has an empty identity tier: the plan can only
        # come from disk
        cold = Embedder(cfg, backend=backend, plan_cache=tmp_path)
        cold.fit(g, Y)
        assert cold.plan_stats == {"built": 0, "hits": 0,
                                   "disk_hits": 1, "disk_stores": 0}
        np.testing.assert_allclose(
            cold.transform(), _oracle(g, Y, 5, laplacian=laplacian),
            atol=1e-4)

    def test_config_and_content_key_the_entry(self, tmp_path):
        g, Y = _cases()["weighted_directed"]
        Embedder(EncoderConfig(K=5), backend="xla",
                 plan_cache=tmp_path).fit(g, Y)
        # different config (laplacian changes w_eff) must MISS
        other = Embedder(EncoderConfig(K=5, laplacian=True),
                         backend="xla", plan_cache=tmp_path)
        other.fit(g, Y)
        assert other.plan_stats["disk_hits"] == 0
        assert other.plan_stats["built"] == 1
        # different content must MISS
        g2 = Graph(g.u.copy(), g.v.copy(),
                   (g.w + 1.0).astype(np.float32), g.n)
        third = Embedder(EncoderConfig(K=5), backend="xla",
                         plan_cache=tmp_path)
        third.fit(g2, Y)
        assert third.plan_stats["disk_hits"] == 0
        # same content in NEW arrays must HIT (content identity, not
        # array identity — the whole point of tier 2)
        fourth = Embedder(EncoderConfig(K=5), backend="xla",
                          plan_cache=tmp_path)
        fourth.fit(Graph(g.u.copy(), g.v.copy(), g.w.copy(), g.n), Y)
        assert fourth.plan_stats == {"built": 0, "hits": 0,
                                     "disk_hits": 1, "disk_stores": 0}


class TestOwnedRows:
    """The owned-rows accumulate path (`EncoderConfig.row_partition`):
    each partitioned Embedder allocates only its (hi - lo, K) slice,
    and the slices concatenate to the unsharded Z — for full-graph
    input AND for the routed sub-multiset a serving shard receives."""

    OWNED_BACKENDS = ["numpy", "xla", "streaming", "pallas"]

    @pytest.mark.parametrize("case", sorted(_cases()))
    @pytest.mark.parametrize("backend", OWNED_BACKENDS)
    def test_owned_slices_concat_to_full_z(self, backend, case):
        from repro.graph.partition import RowPartition
        g, Y = _cases()[case]
        K = int(Y.max()) + 1 if Y.max() >= 0 else 3
        part = RowPartition(g.n, 3)
        ref = _oracle(g, Y, K)
        absent = np.setdiff1d(np.arange(K), Y)
        routed = dict(part.route_graph(g))
        for full in (True, False):
            for i, (lo, hi) in enumerate(part.slices()):
                emb = Embedder(EncoderConfig(K=K, chunk_size=64,
                                             row_partition=(lo, hi)),
                               backend=backend)
                # the full graph, or what a serving shard gets
                emb.fit(g if full else routed[i], Y)
                assert emb.Z_.shape == (hi - lo, K)   # O(n/p), not O(n)
                Z = emb.transform()
                np.testing.assert_allclose(Z, ref[lo:hi], atol=1e-5)
                assert np.isfinite(Z).all() and np.all(Z[:, absent] == 0)

    def test_owned_laplacian_from_full_graph(self):
        """Laplacian degrees come from the graph as passed — the FULL
        unpadded graph keeps the normalizer exact per slice."""
        g, Y = _cases()["weighted_directed"]
        ref = _oracle(g, Y, 5, laplacian=True)
        emb = Embedder(EncoderConfig(K=5, laplacian=True,
                                     row_partition=(30, 100)),
                       backend="xla").fit(g, Y)
        np.testing.assert_allclose(emb.transform(), ref[30:100],
                                   atol=1e-4)

    def test_owned_partial_fit_roundtrip(self):
        g, Y = _cases()["weighted_directed"]
        rng = np.random.default_rng(17)
        emb = Embedder(EncoderConfig(K=5, row_partition=(40, 90)),
                       backend="xla").fit(g, Y)
        Z0 = emb.transform().copy()
        d = Graph(rng.integers(0, g.n, 50).astype(np.int32),
                  rng.integers(0, g.n, 50).astype(np.int32),
                  rng.random(50, dtype=np.float32) + 0.5, g.n)
        emb.partial_fit(d)
        both = Graph(np.concatenate([g.u, d.u]),
                     np.concatenate([g.v, d.v]),
                     np.concatenate([g.w, d.w]), g.n)
        np.testing.assert_allclose(emb.transform(),
                                   _oracle(both, Y, 5)[40:90], atol=1e-4)
        emb.partial_fit(d, sign=-1.0)
        np.testing.assert_allclose(emb.transform(), Z0, atol=1e-4)
        # a delta with no contribution into [lo, hi) is an exact no-op
        out = Graph(np.array([0, 1], np.int32), np.array([2, 3], np.int32),
                    np.ones(2, np.float32), g.n)
        emb.partial_fit(out)
        np.testing.assert_allclose(emb.transform(), Z0, atol=1e-4)

    def test_global_node_ids_and_bounds(self):
        g, Y = _cases()["weighted_directed"]
        emb = Embedder(EncoderConfig(K=5, row_partition=(40, 90)),
                       backend="xla").fit(g, Y)
        ref = _oracle(g, Y, 5)
        np.testing.assert_allclose(
            emb.transform(np.array([40, 60, 89])),
            ref[[40, 60, 89]], atol=1e-5)
        with pytest.raises(IndexError, match="owned"):
            emb.transform(np.array([39]))
        with pytest.raises(IndexError, match="owned"):
            emb.predict(np.array([90]))

    def test_unsupported_backends_and_configs_rejected(self):
        g, Y = _cases()["weighted_directed"]
        # only the distributed backend lacks the owned-rows path; the
        # rejection must name the offender AND the partition-aware
        # alternatives
        emb = Embedder(EncoderConfig(K=5, row_partition=(0, 10),
                                     **CFG),
                       backend="distributed:reduce_scatter")
        with pytest.raises(ValueError, match="owned-rows") as ei:
            emb.plan(g)
        msg = str(ei.value)
        assert "distributed:reduce_scatter" in msg
        for name in ("numpy", "xla", "streaming", "pallas"):
            assert name in msg
        with pytest.raises(ValueError, match="row_partition"):
            EncoderConfig(K=5, row_partition=(10, 10))
        with pytest.raises(ValueError, match="row_partition"):
            EncoderConfig(K=5, row_partition=(-1, 10))
        with pytest.raises(ValueError, match="exceeds"):
            Embedder(EncoderConfig(K=5, row_partition=(0, g.n + 1)),
                     backend="xla").plan(g)

    def test_full_embedding_surfaces_guarded(self):
        g, Y = _cases()["weighted_directed"]
        emb = Embedder(EncoderConfig(K=5, row_partition=(0, 65)),
                       backend="xla").fit(g, Y)
        with pytest.raises(RuntimeError, match="owns only rows"):
            emb.refine()
        with pytest.raises(RuntimeError, match="owns only rows"):
            emb.to_features(16)

    def test_row_partition_keys_the_persistent_cache(self, tmp_path):
        """Resharding must never hit a stale plan: the partition is
        part of the tier-2 key, and same-partition replicas share."""
        g, Y = _cases()["weighted_directed"]
        a = Embedder(EncoderConfig(K=5, row_partition=(0, 65)),
                     backend="xla", plan_cache=tmp_path)
        a.fit(g, Y)
        assert a.plan_stats["disk_stores"] == 1
        b = Embedder(EncoderConfig(K=5, row_partition=(65, 130)),
                     backend="xla", plan_cache=tmp_path)
        b.fit(g, Y)                        # resharded: different key
        assert b.plan_stats["disk_hits"] == 0
        assert b.plan_stats["built"] == 1
        c = Embedder(EncoderConfig(K=5, row_partition=(0, 65)),
                     backend="xla", plan_cache=tmp_path)
        c.fit(g, Y)                        # same partition: shared
        assert c.plan_stats == {"built": 0, "hits": 0,
                                "disk_hits": 1, "disk_stores": 0}
        np.testing.assert_allclose(c.transform(),
                                   _oracle(g, Y, 5)[:65], atol=1e-5)


class TestAutoBackend:
    def test_policy_table_resolution(self):
        from repro.encoder import resolve_auto
        assert resolve_auto(100, 50, device_kind="cpu",
                            device_count=1) == "xla"
        assert resolve_auto(100, 50, device_kind="tpu",
                            device_count=1) == "pallas"
        assert (resolve_auto(100, 50, device_kind="cpu", device_count=8)
                == "distributed:reduce_scatter")
        assert resolve_auto(10, 1 << 40, device_kind="cpu",
                            device_count=1) == "streaming"

    def test_policy_table_is_overridable(self):
        from repro.encoder import AUTO_POLICY, resolve_auto
        AUTO_POLICY.insert(0, ("pin", lambda n, s, k, c: "numpy"))
        try:
            assert resolve_auto(100, 50, device_kind="tpu",
                                device_count=8) == "numpy"
        finally:
            AUTO_POLICY.pop(0)

    def test_auto_fit_resolves_and_matches_oracle(self):
        g, Y = _cases()["weighted_directed"]
        emb = Embedder(EncoderConfig(K=5, **CFG))    # backend="auto"
        assert emb.backend is None                   # deferred to plan()
        emb.fit(g, Y)
        assert emb.backend.name == "xla"             # 1 CPU, small s
        np.testing.assert_allclose(emb.transform(), _oracle(g, Y, 5),
                                   atol=1e-5)
        emb.refit(Y)                                 # identity tier holds
        assert emb.plan_stats["hits"] == 1

    def test_auto_shares_cache_entries_with_explicit_name(self, tmp_path):
        """auto->xla and backend="xla" must address the SAME persistent
        entry (the resolved name keys the cache, not the spec)."""
        g, Y = _cases()["weighted_directed"]
        cfg = EncoderConfig(K=5, **CFG)
        Embedder(cfg, backend="xla", plan_cache=tmp_path).fit(g, Y)
        auto = Embedder(cfg, plan_cache=tmp_path)
        auto.fit(g, Y)
        assert auto.plan_stats["disk_hits"] == 1

    def test_graph_source_front_door(self):
        """fit/plan accept a GraphSource anywhere a Graph is accepted."""
        from repro.graph.sources import SyntheticSource
        src = SyntheticSource("erdos_renyi", n=130, s=700, seed=2,
                              weighted=True)
        g, Y = _cases()["weighted_directed"]
        emb = Embedder(EncoderConfig(K=5), backend="xla").fit(src, Y)
        np.testing.assert_allclose(emb.transform(), _oracle(g, Y, 5),
                                   atol=1e-5)
        with pytest.raises(TypeError, match="GraphSource"):
            Embedder(EncoderConfig(K=5), backend="xla").fit(object(), Y)


class TestEmbedderContract:
    def test_not_fitted_errors(self):
        emb = Embedder(EncoderConfig(K=3))
        for call in (lambda: emb.transform(), lambda: emb.predict(),
                     lambda: emb.refit(), lambda: emb.refine()):
            with pytest.raises(NotFittedError):
                call()

    def test_unknown_backend(self):
        with pytest.raises(KeyError, match="registered"):
            Embedder(EncoderConfig(K=3), backend="tpu-v9")

    def test_label_out_of_range_rejected(self):
        g, _ = _cases()["weighted_directed"]
        emb = Embedder(EncoderConfig(K=3), backend="xla")
        with pytest.raises(ValueError, match=">= K"):
            emb.fit(g, np.full(g.n, 4, np.int32))

    def test_predict_and_transform_slices(self):
        g, truth = sbm(300, 4, 5000, p_in=0.9, seed=5)
        Y = make_labels(300, 4, 0.2, np.random.default_rng(5),
                        true_labels=truth)
        emb = Embedder(EncoderConfig(K=4), backend="xla").fit(g, Y)
        nodes = np.array([4, 8, 15], np.int32)
        np.testing.assert_array_equal(emb.transform(nodes),
                                      emb.transform()[nodes])
        mask = Y < 0
        acc = (emb.predict()[mask] == truth[mask]).mean()
        assert acc > 0.85, acc

    def test_dtype_config(self):
        g, Y = _cases()["weighted_directed"]
        emb = Embedder(EncoderConfig(K=5, dtype="bfloat16"),
                       backend="xla").fit(g, Y)
        assert emb.transform().dtype == jnp.bfloat16

    def test_refine_recovers_sbm(self):
        g, truth = sbm(200, 3, 4000, p_in=0.95, seed=8)
        emb = Embedder(EncoderConfig(K=3, refine_iters=8), backend="xla")
        emb.fit(g, np.full(200, -1, np.int32))
        emb.refine(jax.random.PRNGKey(1))
        import itertools
        best = max((emb.labels_ == np.array(p)[truth]).mean()
                   for p in itertools.permutations(range(3)))
        assert best > 0.85, best

    def test_out_of_range_nodes_rejected(self):
        """jnp gather silently clamps; the front door must raise."""
        g, Y = _cases()["weighted_directed"]
        emb = Embedder(EncoderConfig(K=5), backend="xla").fit(g, Y)
        with pytest.raises(IndexError, match="node ids"):
            emb.transform(np.array([g.n]))
        with pytest.raises(IndexError, match="node ids"):
            emb.predict(np.array([-1]))

    def test_refine_twice_rebootstraps(self):
        """refine() must pin only the FIT-time supervised labels — a
        second refine with a new key re-bootstraps the unknowns instead
        of freezing on round one's clustering."""
        g = erdos_renyi(90, 400, seed=4, weighted=True)  # no communities
        emb = Embedder(EncoderConfig(K=4, refine_iters=3), backend="xla")
        emb.fit(g, np.full(90, -1, np.int32))
        L1 = emb.refine(jax.random.PRNGKey(1)).labels_.copy()
        L2 = emb.refine(jax.random.PRNGKey(2)).labels_.copy()
        assert (L1 != L2).any()            # unknowns were re-bootstrapped
        # supervised pins survive repeated refines
        Y = np.full(90, -1, np.int32)
        Y[[0, 5, 9, 14]] = [0, 1, 2, 3]
        emb2 = Embedder(EncoderConfig(K=4, refine_iters=3), backend="xla")
        emb2.fit(g, Y).refine(jax.random.PRNGKey(3))
        emb2.refine(jax.random.PRNGKey(4))
        np.testing.assert_array_equal(emb2.labels_[Y >= 0], Y[Y >= 0])

    def test_register_custom_backend(self):
        """New execution strategies plug in without touching call sites."""
        @register_backend("test:negated")
        class NegatedXla(get_backend("xla").__class__):
            pass
        try:
            g, Y = _cases()["weighted_directed"]
            emb = Embedder(EncoderConfig(K=5), backend="test:negated")
            emb.fit(g, Y)
            np.testing.assert_allclose(emb.transform(), _oracle(g, Y, 5),
                                       atol=1e-5)
        finally:
            from repro.encoder import backends as B
            del B._REGISTRY["test:negated"]


class TestServiceOnEmbedder:
    def test_service_runs_on_partial_fit(self):
        """serving.EmbeddingService delta path == Embedder.partial_fit;
        its delta-vs-rebuild self-check holds through mixed traffic."""
        from repro.serving import EmbeddingService, GraphStore
        rng = np.random.default_rng(3)
        g, truth = sbm(150, 4, 2000, p_in=0.9, seed=3)
        Y = make_labels(150, 4, 0.3, rng, true_labels=truth)
        svc = EmbeddingService(GraphStore(g, Y, 4))
        assert svc.embedder.backend.name == "streaming"
        for _ in range(4):
            b = int(rng.integers(1, 60))
            svc.apply_edge_delta(rng.integers(0, 150, b).astype(np.int32),
                                 rng.integers(0, 150, b).astype(np.int32),
                                 rng.random(b).astype(np.float32))
        live = svc.store.edges()
        np.testing.assert_allclose(np.asarray(svc.Z),
                                   _oracle(live, svc.Y_epoch, 4),
                                   atol=1e-4)
        # quiet store -> rebuilds reuse the same base arrays -> plan hits
        svc.compact()
        svc.refresh()
        assert svc.embedder.plan_stats["hits"] >= 1


class TestWorkCounters:
    """Each embed counts its work from the plan's static shapes and the
    host labels: `repro_kernel_slots_total` (what the device runs over,
    padding included) and `repro_kernel_contributions_total` split by
    whether the donor's label is known."""

    @pytest.fixture(autouse=True)
    def fresh_obs(self):
        obs.configure(enabled=True)
        obs.reset()
        yield
        obs.reset()

    @staticmethod
    def _counts(backend):
        r = obs.registry()
        return (r.counter_value("repro_kernel_slots_total",
                                backend=backend),
                r.counter_value("repro_kernel_contributions_total",
                                backend=backend, donor="labeled"),
                r.counter_value("repro_kernel_contributions_total",
                                backend=backend, donor="unlabeled"))

    @staticmethod
    def _pallas_slots(dst, n_rows):
        """T * bpt * EB at CFG's tile_n = 64 and edge_block = 128."""
        per_tile = np.bincount(dst // 64, minlength=-(-n_rows // 64))
        return per_tile.size * max(1, -(-int(per_tile.max()) // 128)) * 128

    @pytest.mark.parametrize("backend", ["pallas", "streaming"])
    def test_fit_and_refit(self, backend):
        g = erdos_renyi(300, 2000, seed=5, weighted=True)
        rng = np.random.default_rng(5)
        Ys = [make_labels(300, 4, 0.3, rng), make_labels(300, 4, 0.6, rng)]
        if backend == "pallas":
            slots = self._pallas_slots(np.concatenate([g.u, g.v]), 300)
        else:
            slots = 2 * 8 * 256     # 8 chunks of 256 edges, tail padded
        emb = Embedder(EncoderConfig(K=4, **CFG), backend=backend)
        labeled = 0
        for i, Y in enumerate(Ys, 1):
            if i == 1:
                emb.fit(g, Y)
            else:
                emb.refit(Y)
            labeled += int((Y[g.u] >= 0).sum() + (Y[g.v] >= 0).sum())
            assert self._counts(backend) == (i * slots, labeled,
                                             i * 2 * g.s - labeled)

    @pytest.mark.parametrize("backend", ["pallas", "streaming"])
    def test_row_partition_counts_owned_contributions(self, backend):
        g = erdos_renyi(300, 2000, seed=6, weighted=True)
        Y = make_labels(300, 4, 0.4, np.random.default_rng(6))
        lo, hi = 100, 220
        into_u, into_v = (g.u >= lo) & (g.u < hi), (g.v >= lo) & (g.v < hi)
        dst = np.concatenate([g.u[into_u], g.v[into_v]]) - lo
        donors = np.concatenate([g.v[into_u], g.u[into_v]])
        emb = Embedder(EncoderConfig(K=4, row_partition=(lo, hi), **CFG),
                       backend=backend)
        emb.fit(g, Y)
        slots, labeled, unlabeled = self._counts(backend)
        assert labeled == (Y[donors] >= 0).sum()
        assert labeled + unlabeled == dst.size
        if backend == "pallas":
            assert slots == self._pallas_slots(dst, hi - lo)
        else:           # a chunk row is one contribution here
            assert slots == -(-dst.size // 256) * 256

    @pytest.mark.parametrize("backend", ["pallas", "streaming"])
    def test_off_counts_nothing_and_builds_no_donor_counts(self, backend):
        """With obs off the plan never pays for the donor counts."""
        obs.configure(enabled=False)
        g = erdos_renyi(300, 2000, seed=8)
        Y = make_labels(300, 4, 0.3, np.random.default_rng(8))
        emb = Embedder(EncoderConfig(K=4, **CFG), backend=backend)
        emb.fit(g, Y).refit(Y)
        assert emb._plan._donors is None
        assert not obs.registry().series_names()

    def test_rejected_labels_not_timed(self):
        g = erdos_renyi(300, 2000, seed=9)
        emb = Embedder(EncoderConfig(K=4, **CFG), backend="streaming")
        with pytest.raises(ValueError, match="label"):
            emb.fit(g, np.full(300, 4, np.int32))
        assert obs.registry().hist_summary(
            "repro_encoder_fit_seconds", backend="streaming")["count"] == 0
        emb.fit(g, make_labels(300, 4, 0.3, np.random.default_rng(9)))
        assert obs.registry().hist_summary(
            "repro_encoder_fit_seconds", backend="streaming")["count"] == 1

    def test_refine_counts_every_contribution_labeled(self):
        g, truth = sbm(120, 3, 900, p_in=0.9, seed=7)
        Y = make_labels(120, 3, 0.2, np.random.default_rng(7),
                        true_labels=truth)
        emb = Embedder(EncoderConfig(K=3, refine_iters=2, **CFG),
                       backend="pallas").fit(g, Y)
        before = self._counts("pallas")
        emb.refine()
        after = self._counts("pallas")
        slots = self._pallas_slots(np.concatenate([g.u, g.v]), 120)
        # refine_iters rounds plus the final embed, every donor labeled
        assert [a - b for a, b in zip(after, before)] == \
            [3 * slots, 3 * 2 * g.s, 0]


class TestClassAxisWeights:
    """The pallas backend scatters the packed edge weight under each
    slot's class and scales Z's columns by the class weights 1/n_k once,
    instead of weighting every slot by its donor's Wv."""

    def test_refine_matches_oracle(self):
        g, Y = _cases()["weighted_directed"]
        emb = Embedder(EncoderConfig(K=5, refine_iters=2, **CFG),
                       backend="pallas", plan_cache=None).fit(g, Y)
        emb.refine(jax.random.PRNGKey(2))
        labels = np.asarray(emb.labels_)
        np.testing.assert_allclose(np.asarray(emb.Z_),
                                   _oracle(g, labels, 5), atol=1e-5)
        # the delta paths' per-node weights follow the refined labels
        np.testing.assert_array_equal(
            np.asarray(emb.Wv_),
            np.asarray(make_w(jnp.asarray(labels), 5)))

    def test_one_gather_over_the_packed_slots(self):
        """Only the donor's label is gathered per packed slot: the
        weight 1/n_k is applied per column, so no second slot-sized
        gather (of Wv) is traced."""
        from repro.core.gee import class_weights
        from jax.extend.core import ClosedJaxpr, Jaxpr
        g, Y = _cases()["weighted_directed"]
        emb = Embedder(EncoderConfig(K=5, **CFG), backend="pallas",
                       plan_cache=None).fit(g, Y)
        plan, slots = emb._plan, emb._plan.data["rows"].size
        Yj = jnp.asarray(Y)
        closed = jax.make_jaxpr(
            lambda y, c: emb.backend.embed(plan, y, c)[0]
        )(Yj, class_weights(Yj, 5))

        def eqns(jaxpr):
            for e in jaxpr.eqns:
                yield e
                for p in e.params.values():
                    for sub in (p if isinstance(p, (list, tuple)) else [p]):
                        if isinstance(sub, ClosedJaxpr):
                            yield from eqns(sub.jaxpr)
                        elif isinstance(sub, Jaxpr):
                            yield from eqns(sub)

        gathers = [e for e in eqns(closed.jaxpr)
                   if e.primitive.name == "gather"
                   and e.invars[1].aval.size == slots]
        assert len(gathers) == 1
