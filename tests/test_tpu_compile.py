"""The main path's Pallas kernels compile for a TPU v5e at soc-pokec
widths (n = 1.6M, 30M edges, K = 50), with ``interpret=False``; and the
four-chip refit's embed runs the scatter kernel on each chip of a v5e
2x2 at orkut-groups' size (n = 3.0M, 327M edges).

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
import re

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
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiles_with_kernel(fn, name, *args):
    """Compile `fn` and find the kernel as the HLO instruction `name`
    (`%name.1 = ... custom-call(...)`): the name the device trace and
    the benchmark's readers know the kernel by."""
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert re.search(rf"%{name}(\.\d+)? = [^\n]*custom-call\(", text), \
        [ln.strip()[:80] for ln in text.splitlines() if "custom-call(" in ln]
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
        "gee_scatter_pallas",
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
        "gee_delta_renorm",
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

    _compiles_with_kernel(f, "topk_fused",
                          _spec((m, K), jnp.float32, one_chip),
                          _spec((nq, K), jnp.float32, one_chip),
                          _spec((nq,), jnp.int32, one_chip))


@pytest.mark.parametrize("kernel", ["gee_scatter_pallas", "gee_delta_renorm",
                                    "topk_fused"])
def test_kernel_name_survives_enclosing_jit(one_chip, kernel):
    """Each kernel keeps its name when its body is traced from a plain
    function inside another jit (`__wrapped__`: the kernel's own jit
    taken away), as when its gathers are fused into one program with
    it; without `pallas_call(name=...)` it would take the outer jit's
    name.  Small shapes: only the name is checked."""
    t, eb = 8, (8, 2, 1, EB)
    specs = {
        "gee_scatter_pallas": (
            lambda r, c, v: gee_scatter_pallas.__wrapped__(
                r, c, v, num_tiles=t, tile_n=TILE_N, kdim=KDIM,
                interpret=False) * 2.0,
            [(eb, jnp.int32), (eb, jnp.int32), (eb, jnp.float32)]),
        "gee_delta_renorm": (
            lambda z, r, c, v: gee_delta_renorm.__wrapped__(
                z, r, c, v, tile_n=TILE_N, interpret=False)[1] * 2.0,
            [((t * TILE_N, K), jnp.float32), (eb, jnp.int32),
             (eb, jnp.int32), (eb, jnp.float32)]),
        "topk_fused": (
            lambda z, q, qn: topk_fused.__wrapped__(
                z, q, qn, k=10, bucket=1 << 12, interpret=False)[0] * 2.0,
            [((1 << 13, K), jnp.float32), ((8, K), jnp.float32),
             ((8,), jnp.int32)]),
    }
    fn, shapes = specs[kernel]

    def outer_step(*args):          # a plain function: no jit of its own
        return fn(*args)

    _compiles_with_kernel(outer_step, kernel,
                          *[_spec(sh, dt, one_chip) for sh, dt in shapes])


def test_reduce_scatter_embed_compiles_on_four_chips(topo):
    """`distributed:reduce_scatter`'s embed (`core.distributed
    ._program`) on a 2x2 mesh: each chip's (T, 29, 1, EB) packed
    slots (n_pad = 3.0M; the fullest tile of `er-4chip`'s graph fills
    29 blocks) through the kernel, one cross-chip reduction that runs
    as an all-reduce of its own, within a chip's 16 GB."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core import distributed as D
    n = 3_000_000
    mesh = Mesh(np.asarray(topo.devices), (D.AXIS,))
    edges, rep = NamedSharding(mesh, P(D.AXIS)), NamedSharding(mesh, P())
    slots = (4 * -(-n // TILE_N), 29, 1, EB)
    compiled = _compiles_with_kernel(
        D._program(K, n, mesh, TILE_N, False),
        "gee_scatter_pallas",
        _spec(slots, jnp.int32, edges), _spec(slots, jnp.int32, edges),
        _spec(slots, jnp.float32, edges), _spec((n,), jnp.int32, rep),
        _spec((K,), jnp.float32, rep))
    # an instruction of the entry computation named all-reduce, the name
    # the device trace shows for it (not folded into a fusion)
    text = compiled.as_text()
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    assert re.search(r"%all-reduce(\.\d+)? = [^\n]*all-reduce\(", entry)
    ma = compiled.memory_analysis()
    assert (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes) < 16e9
