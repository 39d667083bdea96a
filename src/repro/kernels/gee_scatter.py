"""GEE edge-scatter Pallas kernel: the paper's atomic ``writeAdd`` loop
as a TPU-native one-hot matmul accumulation.

The CPU algorithm does, per edge, a random-index read-modify-write into
Z — exactly the op TPUs don't have.  The TPU formulation:

  * edges are pre-sorted by destination tile (``dst // TILE_N``) and
    packed into uniform edge blocks (host-side, O(s log s) once);
  * grid = (num_tiles, blocks_per_tile); the Z tile (TILE_N, K) stays
    resident in VMEM across the inner grid dimension (revisiting
    BlockSpec), so all accumulation happens on-chip;
  * each edge block is a lane-dense (1, EB) row; the scatter becomes
    two transposed one-hot expansions and one dense matmul on the MXU:
        Rt[r, e] = [row_local(e) == r]        (TILE_N, EB)
        Ct[k, e] = [cls(e) == k] * val(e)     (K, EB)
        Z_tile += Rt @ Ct^T
    No RMW race is possible: one grid instance owns the tile, and the
    matmul reduction replaces the atomic adds (deterministically).

Packed layout: (T, BPT, 1, EB).  The unit axis makes the block's last
two dimensions (1, EB) — equal to the array's own and a multiple of
128 lanes — which is what Mosaic's (8, 128) tiling rule accepts; a
(T, BPT, EB) array blocked (1, 1, EB) is refused on the chip.  EB must
be a multiple of 128 for the compiled kernel (any size interprets).

The matmul runs at ``Precision.HIGHEST``: the one-hot is exact in any
precision but the values (1/class-count times the edge weight) are
not exact in bf16, and the f32 contract with the numpy oracle is what
a caller gets from every other backend.

This mirrors how the paper's cache analysis maps to the TPU memory
hierarchy: their "Z(u,:) stays in processor cache during a vertex's edge
list" becomes "the Z tile stays in VMEM during its edge blocks"; their
cache-missing Z(v,:) random writes disappear entirely because sorting
made the destination local.
"""
from __future__ import annotations

import functools
from typing import Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_N = 256          # Z rows per VMEM tile
EDGE_BLOCK = 512      # edges per inner grid step

#: platforms with a real pallas lowering — everywhere else the kernels
#: run in the interpreter (correctness path, NOT kernel performance)
COMPILED_PLATFORMS = ("tpu", "gpu")


def resolve_interpret(interpret: Union[bool, str] = "auto") -> bool:
    """Resolve an ``interpret`` knob to a concrete bool for pallas_call.

    ``"auto"`` (the `EncoderConfig` default) compiles on TPU/GPU —
    platforms where pallas has a native lowering — and falls back to
    the interpreter elsewhere (CPU).  An explicit True/False is passed
    through: True forces the interpreter (debugging), False forces
    compilation (fails loudly where no lowering exists, which is the
    point — a silent interpreter fallback is how a "fast kernel" path
    ends up measured in pure Python)."""
    if interpret == "auto" or interpret is None:
        return jax.default_backend() not in COMPILED_PLATFORMS
    return bool(interpret)


def interpret_mode_name(interpret: bool) -> str:
    """Human/metric label for a resolved interpret flag."""
    return "interpret" if interpret else "compiled"


def edge_block_spec(eb: int) -> pl.BlockSpec:
    """Block spec of one (1, EB) edge block of a (T, BPT, 1, EB) packed
    array on the (tile, block) grid — shared by every kernel that walks
    the destination-tiled layout."""
    return pl.BlockSpec((None, None, 1, eb), lambda t, b: (t, b, 0, 0))


def accumulate_block(rows, cls, val, *, tile_n: int, kdim: int):
    """One edge block's scatter as a matmul: (1, EB) tile-local rows,
    classes and values -> the (tile_n, kdim) contribution."""
    eb = rows.shape[-1]
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (tile_n, eb), 0)
    cls_iota = jax.lax.broadcasted_iota(jnp.int32, (kdim, eb), 0)
    Rt = (row_iota == rows).astype(jnp.float32)               # (TILE_N, EB)
    Ct = (cls_iota == cls).astype(jnp.float32) * val          # (K, EB)
    return jax.lax.dot_general(
        Rt, Ct, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)                   # (TILE_N, K)


def _kernel(rows_ref, cls_ref, val_ref, z_ref, *, tile_n: int, kdim: int):
    b = pl.program_id(1)

    @pl.when(b == 0)
    def _init():
        z_ref[...] = jnp.zeros_like(z_ref)

    z_ref[...] += accumulate_block(
        rows_ref[...], cls_ref[...], val_ref[...].astype(jnp.float32),
        tile_n=tile_n, kdim=kdim)


@functools.partial(jax.jit, static_argnames=("num_tiles", "tile_n",
                                             "kdim", "interpret"))
def gee_scatter_pallas(rows, cls, val, *, num_tiles: int, tile_n: int,
                       kdim: int, interpret: Union[bool, str] = "auto"):
    """rows/cls/val: (T, BPT, 1, EB) packed edge blocks (see
    ops.pack_edges).

    Returns Z (num_tiles * tile_n, kdim) float32."""
    interpret = resolve_interpret(interpret)
    T, BPT, _, EB = rows.shape
    assert T == num_tiles
    eb_spec = edge_block_spec(EB)
    z_spec = pl.BlockSpec((tile_n, kdim), lambda t, b: (t, 0))
    return pl.pallas_call(
        functools.partial(_kernel, tile_n=tile_n, kdim=kdim),
        grid=(T, BPT),
        in_specs=[eb_spec, eb_spec, eb_spec],
        out_specs=z_spec,
        out_shape=jax.ShapeDtypeStruct((T * tile_n, kdim), jnp.float32),
        interpret=interpret,
        name="gee_scatter_pallas",     # the device trace's name for it
    )(rows, cls, val)
