"""Graph substrate: generators, IO, partition planning."""
import numpy as np
import pytest

from repro.graph.edges import Graph, make_labels
from repro.graph.generators import erdos_renyi, powerlaw, sbm
from repro.graph.io import ShardedEdgeReader, load_graph, save_graph
from repro.graph.partition import shuffle_edges


def test_generator_shapes_and_ranges():
    g = erdos_renyi(100, 1000, seed=0)
    g.validate()
    assert g.s == 1000 and g.n == 100
    gp = powerlaw(100, 1000, seed=0)
    gp.validate()
    gs, labels = sbm(100, 4, 1000, seed=0)
    gs.validate()
    assert labels.shape == (100,) and labels.max() < 4


def test_sbm_is_assortative():
    g, labels = sbm(500, 5, 20000, p_in=0.9, seed=1)
    same = (labels[g.u] == labels[g.v]).mean()
    assert same > 0.8       # ~p_in + chance
    g2, _ = sbm(500, 5, 20000, p_in=0.2, seed=1)


def test_symmetrize_doubles_edges():
    g = erdos_renyi(50, 200, seed=2)
    gs = g.symmetrize()
    assert gs.s == 400
    d1 = g.degrees()
    np.testing.assert_allclose(gs.degrees(), 2 * d1)


def test_pad_is_noop_for_gee():
    import jax.numpy as jnp
    from repro.core.gee import gee
    g = erdos_renyi(60, 123, seed=3, weighted=True)
    Y = make_labels(60, 4, 0.5, np.random.default_rng(3))
    Z1 = np.asarray(gee(jnp.asarray(g.u), jnp.asarray(g.v),
                        jnp.asarray(g.w), jnp.asarray(Y), K=4, n=60))
    gp = g.pad_to(160)
    Z2 = np.asarray(gee(jnp.asarray(gp.u), jnp.asarray(gp.v),
                        jnp.asarray(gp.w), jnp.asarray(Y), K=4, n=60))
    np.testing.assert_allclose(Z1, Z2, atol=1e-6)


def test_pad_preserves_laplacian_degrees():
    """Regression (ISSUE 2): padded zero-weight self-loops on node 0
    must not perturb `degrees()` or the Laplacian deg precompute —
    including when node 0 is isolated (deg 0) and would be most
    sensitive to a phantom self-loop."""
    import jax.numpy as jnp
    from repro.core.gee import gee
    g = erdos_renyi(60, 123, seed=7, weighted=True)
    # isolate node 0 so any phantom degree contribution is visible
    keep = (g.u != 0) & (g.v != 0)
    g = Graph(g.u[keep], g.v[keep], g.w[keep], g.n)
    Y = make_labels(60, 4, 0.5, np.random.default_rng(7))
    gp = g.pad_to(256)
    assert gp.n == g.n and gp.s == 256
    np.testing.assert_array_equal(g.degrees(), gp.degrees())
    assert gp.degrees()[0] == 0.0
    Z1 = np.asarray(gee(jnp.asarray(g.u), jnp.asarray(g.v),
                        jnp.asarray(g.w), jnp.asarray(Y), K=4, n=60,
                        laplacian=True))
    Z2 = np.asarray(gee(jnp.asarray(gp.u), jnp.asarray(gp.v),
                        jnp.asarray(gp.w), jnp.asarray(Y), K=4, n=60,
                        laplacian=True))
    np.testing.assert_allclose(Z1, Z2, atol=1e-6)
    # same invariant through the unified API's deg precompute (the
    # encoder plans degrees from the unpadded graph by construction)
    from repro.encoder import Embedder, EncoderConfig
    Zp = Embedder(EncoderConfig(K=4, laplacian=True),
                  backend="xla").fit(gp, Y).transform()
    np.testing.assert_allclose(Z1, Zp, atol=1e-6)


def test_pad_empty_graph_rejected():
    g = Graph(np.zeros(0, np.int32), np.zeros(0, np.int32),
              np.zeros(0, np.float32), 0)
    with pytest.raises(AssertionError, match="no nodes"):
        g.pad_to(8)


def test_io_roundtrip_and_sharded_reader(tmp_path):
    g = erdos_renyi(100, 999, seed=4, weighted=True)
    path = str(tmp_path / "g.npz")
    save_graph(path, g)
    g2 = load_graph(path)
    np.testing.assert_array_equal(g.u, g2.u)
    np.testing.assert_allclose(g.w, g2.w)

    # two hosts stream disjoint slices covering everything
    seen = []
    for host in (0, 1):
        for chunk in ShardedEdgeReader(path, host, 2, chunk_size=100):
            seen.append(chunk.u)
    assert sum(len(x) for x in seen) == g.s
    np.testing.assert_array_equal(np.concatenate(seen), g.u)


def test_sbm_deterministic_per_seed():
    g1, l1 = sbm(300, 4, 3000, p_in=0.9, seed=11)
    g2, l2 = sbm(300, 4, 3000, p_in=0.9, seed=11)
    np.testing.assert_array_equal(g1.u, g2.u)
    np.testing.assert_array_equal(g1.v, g2.v)
    np.testing.assert_array_equal(l1, l2)
    assert g1.fingerprint() == g2.fingerprint()
    g3, _ = sbm(300, 4, 3000, p_in=0.9, seed=12)
    assert g1.fingerprint() != g3.fingerprint()


def test_sbm_empty_block_handling():
    """With n << K some blocks get no members; the intra-edge sampler
    (sorted-by-label indexing) must still confine intra edges to the
    source's own block and never index out of range."""
    found = False
    for seed in range(40):
        g, labels = sbm(8, 6, 400, p_in=1.0, seed=seed)
        g.validate()
        # p_in=1.0 -> EVERY edge is intra: endpoints share a block
        np.testing.assert_array_equal(labels[g.u], labels[g.v])
        if np.bincount(labels, minlength=6).min() == 0:
            found = True                    # an actually-empty block
    assert found, "no seed produced an empty block; weaken n or raise K"


def test_powerlaw_degree_skew():
    n, s = 1000, 50_000
    g = powerlaw(n, s, alpha=1.5, seed=3)
    g.validate()
    out = np.bincount(g.u, minlength=n)
    mean = s / n
    # rank-1 node dominates: far above mean, and the top 1% of sources
    # carry a disproportionate share of all edges (Zipf endpoints)
    assert out[0] > 10 * mean
    top = np.sort(out)[::-1][: n // 100].sum()
    assert top / s > 0.3
    # destinations stay uniform-ish (only sources are skewed)
    indeg = np.bincount(g.v, minlength=n)
    assert indeg.max() < 5 * mean


def test_weighted_erdos_renyi_roundtrip(tmp_path):
    g = erdos_renyi(120, 800, seed=9, weighted=True)
    assert g.w.dtype == np.float32 and (g.w >= 0.5).all()
    assert not np.allclose(g.w, 1.0)           # actually weighted
    path = str(tmp_path / "w.npz")
    save_graph(path, g)
    g2 = load_graph(path)
    np.testing.assert_array_equal(g.u, g2.u)
    np.testing.assert_array_equal(g.v, g2.v)
    np.testing.assert_array_equal(g.w, g2.w)
    assert g2.n == g.n
    assert g.fingerprint() == g2.fingerprint()


def test_mmap_fast_path_matches_streaming(tmp_path):
    """ROADMAP satellite: uncompressed snapshots take the mmap path;
    chunks must be identical to the streaming decode, per host slice."""
    from repro.graph.io import is_mmapable
    g = erdos_renyi(100, 999, seed=4, weighted=True)
    comp = str(tmp_path / "c.npz")
    stored = str(tmp_path / "u.npz")
    save_graph(comp, g)
    save_graph(stored, g, compressed=False)
    assert not is_mmapable(comp) and is_mmapable(stored)

    for host in (0, 1, 2):
        mm = list(ShardedEdgeReader(stored, host, 3, chunk_size=100,
                                    mmap=True))
        st = list(ShardedEdgeReader(stored, host, 3, chunk_size=100,
                                    mmap=False))
        assert len(mm) == len(st)
        for a, b in zip(mm, st):
            np.testing.assert_array_equal(np.asarray(a.u), b.u)
            np.testing.assert_array_equal(np.asarray(a.v), b.v)
            np.testing.assert_array_equal(np.asarray(a.w), b.w)
            assert a.n == b.n

    # auto-detection: stored file maps, compressed file streams; forcing
    # mmap on a compressed file is a loud error, not silent decode
    assert ShardedEdgeReader(stored, 0, 1).mmap
    assert not ShardedEdgeReader(comp, 0, 1).mmap
    with pytest.raises(ValueError, match="compressed"):
        list(ShardedEdgeReader(comp, 0, 1, mmap=True))
    # and the mmap'd chunks are zero-copy views of the file
    first = next(iter(ShardedEdgeReader(stored, 0, 1, mmap=True)))
    assert isinstance(first.u, np.memmap)


def test_shuffle_balances_owners():
    g = powerlaw(1024, 32768, seed=5)     # skewed sources
    gs = shuffle_edges(g, seed=1)
    p, per, rows = 8, gs.s // 8, -(-gs.n // 8)
    # [shard, owner] contribution counts: each shard's destinations
    # binned by the row shard that owns them
    hist = np.stack([np.bincount(
        np.concatenate([gs.u[i * per:(i + 1) * per],
                        gs.v[i * per:(i + 1) * per]]) // rows,
        minlength=p) for i in range(p)])
    per_shard = hist.sum(1)
    assert per_shard.max() / per_shard.min() < 1.05
