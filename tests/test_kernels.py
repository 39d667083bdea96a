"""Pallas kernels vs. pure-jnp oracles (interpret mode): shape/dtype
sweeps per the brief."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.graph.edges import make_labels
from repro.graph.generators import erdos_renyi, powerlaw
from repro.kernels import ops, ref


class TestGeeScatterKernel:
    @pytest.mark.parametrize("n,s,K", [
        (100, 500, 5), (1000, 8000, 12), (257, 1999, 50), (64, 64, 3),
    ])
    @pytest.mark.parametrize("tile_n,edge_block", [(128, 128), (64, 256)])
    def test_matches_oracle(self, n, s, K, tile_n, edge_block):
        g = erdos_renyi(n, s, seed=n + s, weighted=True)
        Y = make_labels(n, K, 0.3, np.random.default_rng(n))
        Z = ops.gee_pallas(g.u, g.v, g.w, jnp.asarray(Y), K=K, n=n,
                           tile_n=tile_n, edge_block=edge_block)
        Zr = ref.gee_ref(jnp.asarray(g.u), jnp.asarray(g.v),
                         jnp.asarray(g.w), jnp.asarray(Y), n, K)
        np.testing.assert_allclose(np.asarray(Z), np.asarray(Zr),
                                   atol=1e-5)

    def test_skewed_destinations(self):
        """Power-law graphs stress the per-tile bucket padding."""
        g = powerlaw(300, 5000, seed=9)
        Y = make_labels(300, 8, 0.25, np.random.default_rng(9))
        Z = ops.gee_pallas(g.u, g.v, g.w, jnp.asarray(Y), K=8, n=300,
                           tile_n=64, edge_block=128)
        Zr = ref.gee_ref(jnp.asarray(g.u), jnp.asarray(g.v),
                         jnp.asarray(g.w), jnp.asarray(Y), 300, 8)
        np.testing.assert_allclose(np.asarray(Z), np.asarray(Zr),
                                   atol=1e-5)

    def test_all_unlabeled_zero(self):
        g = erdos_renyi(64, 256, seed=1)
        Y = jnp.full((64,), -1, jnp.int32)
        Z = ops.gee_pallas(g.u, g.v, g.w, Y, K=4, n=64,
                           tile_n=64, edge_block=64)
        assert np.all(np.asarray(Z) == 0)


class TestPackEdges:
    """Host-side packing edge cases (ISSUE 2): the packed blocks must
    round-trip to exactly the XLA scatter result."""

    @staticmethod
    def _scatter_oracle(dst, cls, val, n, K):
        Z = np.zeros((n, K), np.float32)
        np.add.at(Z, (np.asarray(dst), np.asarray(cls)), np.asarray(val))
        return Z

    @staticmethod
    def _unpack_scatter(rows, clsb, valb, T, tile_n, n, K):
        """Replay the packed blocks on the host: tile-local rows become
        global rows; padded slots carry val = 0 and cancel out."""
        Z = np.zeros((T * tile_n, K), np.float32)
        for t in range(T):
            r = rows[t].reshape(-1) + t * tile_n
            c = clsb[t].reshape(-1)
            x = valb[t].reshape(-1)
            np.add.at(Z, (r, c), x)
        return Z[:n]

    def _roundtrip(self, dst, cls, val, n, K, tile_n=64, edge_block=32):
        rows, clsb, valb, T = ops.pack_edges(dst, cls, val, n,
                                             tile_n, edge_block)
        assert rows.shape == clsb.shape == valb.shape
        # (T, BPT, 1, EB): the unit axis is the kernels' block row
        assert rows.shape[0] == T and rows.shape[2:] == (1, edge_block)
        got = self._unpack_scatter(rows, clsb, valb, T, tile_n, n, K)
        np.testing.assert_allclose(
            got, self._scatter_oracle(dst, cls, val, n, K), atol=1e-6)

    def test_empty_edge_list(self):
        dst = np.zeros(0, np.int32)
        self._roundtrip(dst, dst.copy(), np.zeros(0, np.float32),
                        n=100, K=4)

    def test_all_edges_one_destination_tile(self):
        rng = np.random.default_rng(11)
        dst = rng.integers(0, 64, 500).astype(np.int32)   # tile 0 only
        cls = rng.integers(0, 4, 500).astype(np.int32)
        val = rng.random(500, dtype=np.float32)
        self._roundtrip(dst, cls, val, n=1000, K=4)

    def test_n_not_multiple_of_tile(self):
        rng = np.random.default_rng(13)
        n = 257                                           # 257 % 64 != 0
        dst = rng.integers(0, n, 900).astype(np.int32)
        cls = rng.integers(0, 5, 900).astype(np.int32)
        val = rng.random(900, dtype=np.float32)
        self._roundtrip(dst, cls, val, n=n, K=5)

    def test_empty_graph_through_pallas_kernel(self):
        """pack_edges empty case end-to-end through gee_pallas."""
        Z = ops.gee_pallas(np.zeros(0, np.int32), np.zeros(0, np.int32),
                           np.zeros(0, np.float32),
                           jnp.zeros(64, jnp.int32), K=4, n=64,
                           tile_n=64, edge_block=64)
        assert np.all(np.asarray(Z) == 0) and Z.shape == (64, 4)


class TestFlashAttentionKernel:
    @pytest.mark.parametrize("B,H,KV,S,D", [
        (1, 2, 2, 64, 16),      # MHA
        (2, 4, 2, 128, 32),     # GQA 2:1
        (1, 8, 1, 128, 16),     # MQA
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_oracle(self, B, H, KV, S, D, dtype):
        ks = jax.random.split(jax.random.PRNGKey(B * 100 + S), 3)
        q = jax.random.normal(ks[0], (B, H, S, D), dtype)
        k = jax.random.normal(ks[1], (B, KV, S, D), dtype)
        v = jax.random.normal(ks[2], (B, KV, S, D), dtype)
        o = ops.flash_attention(q, k, v, bq=32, bk=32)
        orf = ref.flash_attention_ref(q, k, v)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(
            np.asarray(o, np.float32), np.asarray(orf, np.float32),
            atol=tol, rtol=tol)

    @pytest.mark.parametrize("bq,bk", [(16, 64), (64, 16), (128, 128)])
    def test_block_shape_sweep(self, bq, bk):
        ks = jax.random.split(jax.random.PRNGKey(7), 3)
        q = jax.random.normal(ks[0], (1, 4, 128, 32))
        k = jax.random.normal(ks[1], (1, 2, 128, 32))
        v = jax.random.normal(ks[2], (1, 2, 128, 32))
        o = ops.flash_attention(q, k, v, bq=bq, bk=bk)
        orf = ref.flash_attention_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(o), np.asarray(orf),
                                   atol=2e-5, rtol=2e-5)

    def test_matches_model_chunked_attention(self):
        """The Pallas kernel and the model's lax.scan flash path are the
        same math — cross-validate them against each other."""
        from repro.models.attention import attn_flash
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        B, H, KV, S, D = 2, 4, 4, 128, 16
        q = jax.random.normal(ks[0], (B, S, H, D))
        k = jax.random.normal(ks[1], (B, S, KV, D))
        v = jax.random.normal(ks[2], (B, S, KV, D))
        pos = jnp.arange(S)
        o_model = attn_flash(q, k, v, pos, pos, causal=True,
                             q_chunk=32, kv_chunk=32)
        o_kernel = ops.flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), bq=32, bk=32).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(o_model),
                                   np.asarray(o_kernel), atol=2e-5)
