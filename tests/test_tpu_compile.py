"""The main path's Pallas kernels compile for a TPU v5e at soc-pokec
widths (n = 1.6M, 30M edges, K = 50), with ``interpret=False``.

The TPU compiler is installed with jaxlib and compiles for a described,
unattached chip: this catches what interpret mode cannot — block
shapes Mosaic's tiling refuses, primitives it cannot lower, and VMEM
overruns — without a chip.  Nothing runs; it says nothing about
results or speed.

The topology is described inside a module fixture, never at import:
only one process may hold the TPU library, and a worker that loaded it
at collection would make the others fail.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.gee_scatter import gee_scatter_pallas
from repro.kernels.query_fused import gee_delta_renorm, topk_fused

N, K, KDIM = 1_600_000, 50, 56          # soc-pokec, K rounded up to 8
TILE_N, EB = 256, 512                   # EncoderConfig defaults
T = N // TILE_N


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiles_with_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_gee_scatter_compiles(one_chip):
    """The fit kernel over 2s = 60M packed contributions (BPT = 24:
    the busiest tile of an SBM graph at this shape overflows 19 blocks
    of 512)."""
    bpt = 24
    eb = (T, bpt, 1, EB)
    _compiles_with_kernel(
        lambda r, c, v: gee_scatter_pallas(r, c, v, num_tiles=T,
                                           tile_n=TILE_N, kdim=KDIM,
                                           interpret=False),
        _spec(eb, jnp.int32, one_chip), _spec(eb, jnp.int32, one_chip),
        _spec(eb, jnp.float32, one_chip))


def test_gee_delta_renorm_compiles(one_chip):
    """The serving write path on one of two shards, with a delta batch
    whose busiest tile overflows one edge block (BPT > 1)."""
    n_local = N // 2
    t = n_local // TILE_N
    eb = (t, 3, 1, EB)
    _compiles_with_kernel(
        lambda z, r, c, v: gee_delta_renorm(z, r, c, v, tile_n=TILE_N,
                                            interpret=False),
        _spec((n_local, K), jnp.float32, one_chip),
        _spec(eb, jnp.int32, one_chip), _spec(eb, jnp.int32, one_chip),
        _spec(eb, jnp.float32, one_chip))


@pytest.mark.parametrize("normalize", [False, True])
def test_topk_fused_compiles(one_chip, normalize):
    """The serving read path: 64 top-10 queries against one shard's
    800k-row slice in 16384-row blocks (the engine's default)."""
    m, nq = N // 2, 64

    def f(z, q, qn):
        return topk_fused(z, q, qn, k=10, bucket=1 << 14,
                          row_offset=m, normalize=normalize,
                          interpret=False)

    _compiles_with_kernel(f, _spec((m, K), jnp.float32, one_chip),
                          _spec((nq, K), jnp.float32, one_chip),
                          _spec((nq,), jnp.int32, one_chip))
