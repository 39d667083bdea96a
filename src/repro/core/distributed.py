"""Distributed GEE: the paper's shared-memory edge-parallelism mapped to
SPMD collectives.

The paper's Ligra implementation parallelizes the edge loop across cores
that share one coherent DRAM array Z, racing on Z[u, k] and resolving
races with lock-free atomic adds.  On a TPU pod there is no shared
mutable HBM, so the shared array becomes a reduction: every chip
accumulates its own edges' contributions into a private (n, K) partial
Z, and one collective sums the partials.  Addition commutes, so the
order of the chips' adds, which the paper left to the atomics, cannot
change Z beyond float32 rounding.

The edges are sharded over the mesh's one axis, s/p a chip.  Once per
plan, each chip packs its own contributions on the chips into the
destination-tiled slots of the scatter kernel (`pack_sharded`: one
sort, one read-back of the fullest tile, one copy into the slots).  The
slots hold label-free (row, donor, weight) triples, so a refit packs
nothing again.  Each embed (`gee_packed`) gathers every slot's donor
label, runs the kernel (`kernels.gee_scatter`) on every chip into a
full (n, K) partial Z, reduces it with one psum_scatter that leaves
each chip its own row shard, and scales that shard's columns by the
class weights 1/n_k.  Memory: O(n*K) transient, O(n*K/P) resident; the
collective is one reduce-scatter of the (n, K) accumulator.

The embed is one jitted program, built once per key and cached
(`_program`), so a refit dispatches one executable.  The packed slots
arrive sharded over the mesh's edge axis; the labels and the (K,)
class weights arrive replicated.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.gee import class_weights
from repro.kernels.gee_scatter import EDGE_BLOCK, TILE_N, gee_scatter_pallas

AXIS = "edges"


def shard_map(f, mesh, in_specs, out_specs):
    """`jax.shard_map` without the varying-manual-axes check (the
    bodies mix replicated and per-shard values by hand)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def edge_mesh(devices=None) -> Mesh:
    """Flat 1-D mesh over all devices (GEE has no model dimension)."""
    devices = np.asarray(devices if devices is not None else jax.devices())
    return Mesh(devices.reshape(-1), (AXIS,))


def pad_rows(n: int, p: int) -> int:
    return ((n + p - 1) // p) * p


# ---------------------------------------------------------------------------
# shard_map body
# ---------------------------------------------------------------------------


def _body_reduce_scatter(rows, src, w, Y, class_w, *, K, n, tile_n,
                         interpret):
    """One chip's (T, BPT, 1, EB) packed slots through the scatter
    kernel, each slot under its donor's current class (an unlabeled
    donor's -1 matches no column); the (n, K) partial Z reduced over the
    chips, and each chip's row shard scaled by the class weights."""
    Z = gee_scatter_pallas(rows, Y[src], w, num_tiles=rows.shape[0],
                           tile_n=tile_n, kdim=-(-K // 8) * 8,
                           interpret=interpret)
    # reduced flat (a chip's rows are a contiguous run of the row-major
    # Z): on TPU v5e this runs as one all-reduce of the n*K floats and
    # a slice.  Scattered along the rows of (n, K), it becomes a fusion
    # over a layout whose K columns fill 128 lanes, 4.5 ms a step slower
    # at n = 3M, K = 50 on four chips.
    Zs = jax.lax.psum_scatter(Z[:n, :K].reshape(-1), AXIS,
                              scatter_dimension=0, tiled=True)
    return Zs.reshape(-1, K) * class_w


# ---------------------------------------------------------------------------
# reduce_scatter: each chip's contributions packed on the chips
# ---------------------------------------------------------------------------


def _edge_weights(u, v, w, n: int, laplacian: bool):
    """Float32 edge weights, Laplacian-scaled w / sqrt(deg_u deg_v)
    where asked (degrees over the whole padded edge list)."""
    w = w.astype(jnp.float32)
    if laplacian:
        deg = jnp.zeros(n, jnp.float32).at[u].add(w).at[v].add(w)
        scale = jax.lax.rsqrt(jnp.maximum(deg, 1.0))
        w = w * scale[u] * scale[v]
    return w


def _sort_body(u, v, w, *, tile_n, T):
    """One chip's contributions (dst, src, w) = ([u, v], [v, u], [w, w])
    stable-sorted by destination tile in one multi-operand sort, as
    (tile-local row, src, w); each tile's first index and count; and
    the fullest tile's count over all chips."""
    dst = jnp.concatenate([u, v])
    tile, dst, src, w = jax.lax.sort(
        (dst // tile_n, dst, jnp.concatenate([v, u]),
         jnp.concatenate([w, w])), num_keys=1, is_stable=True)
    bounds = jnp.searchsorted(tile, jnp.arange(T + 1, dtype=tile.dtype))
    counts = bounds[1:] - bounds[:-1]
    return (dst - tile * tile_n, src, w, bounds[:-1], counts,
            jax.lax.pmax(jnp.max(counts), AXIS))


def _slot_body(row, src, w, starts, counts, *, bpt, eb):
    """The sorted contributions copied to their (T, bpt, 1, eb) slots:
    tile t's run of counts[t] contributions from starts[t] fills its
    blocks in order, one contiguous window a tile; the rest stay 0
    (w = 0 adds nothing)."""
    width = bpt * eb
    used = jnp.arange(width) < counts[:, None]

    def put(x):
        x = jnp.concatenate([x, jnp.zeros(width, x.dtype)])
        runs = jax.vmap(
            lambda a: jax.lax.dynamic_slice(x, (a,), (width,)))(starts)
        return jnp.where(used, runs, 0).reshape(-1, bpt, 1, eb)
    return put(row), put(src), put(w)


@functools.lru_cache(maxsize=16)
def _sort_program(n: int, mesh: Mesh, tile_n: int, laplacian: bool):
    body = functools.partial(_sort_body, tile_n=tile_n,
                             T=-(-n // tile_n))
    fn = shard_map(body, mesh, in_specs=(P(AXIS),) * 3,
                   out_specs=(P(AXIS),) * 5 + (P(),))

    def sort(u, v, w):
        return fn(u, v, _edge_weights(u, v, w, n, laplacian))
    return jax.jit(sort)


@functools.lru_cache(maxsize=16)
def _slot_program(mesh: Mesh, eb: int, bpt: int):
    body = functools.partial(_slot_body, bpt=bpt, eb=eb)
    return jax.jit(shard_map(body, mesh, in_specs=(P(AXIS),) * 5,
                             out_specs=(P(AXIS),) * 3))


def pack_sharded(u, v, w, *, n: int, mesh: Mesh, tile_n: int = TILE_N,
                 edge_block: int = EDGE_BLOCK, laplacian: bool = False):
    """Pack each chip's contributions, on the chips, into the scatter
    kernel's destination-tiled layout (`kernels.ops.pack_edges`).

    u, v, w: (s,) edges sharded over the mesh's edge axis, s divisible
    by the mesh size.  Chip i's contributions are ([u_i, v_i], [v_i,
    u_i], [w_i, w_i]) (destination, label donor, weight), over n rows in
    T = ceil(n / tile_n) tiles.  Returns (rows, src, w), each (p * T,
    BPT, 1, edge_block) and sharded over the edge axis: chip i's (T,
    BPT, 1, edge_block) slab is `pack_edges` of its own contributions,
    slot for slot, padded with zero blocks to BPT, the block count of
    the fullest tile over all chips.  That count is the one value read
    back to the host: it fixes the static shape."""
    *runs, fullest = _sort_program(n, mesh, tile_n, laplacian)(u, v, w)
    bpt = max(1, -(-int(fullest) // edge_block))
    return _slot_program(mesh, edge_block, bpt)(*runs)


def gee_packed(rows, src, w, Y, class_w, *, K: int, n: int, mesh: Mesh,
               tile_n: int = TILE_N, interpret="auto"):
    """The reduce_scatter embed over `pack_sharded`'s slots: Z (n, K)
    row-sharded over the mesh.  Y: (n,) labels and class_w: (K,) class
    weights 1/n_k of Y, both replicated."""
    return _program(K, n, mesh, tile_n, interpret)(
        rows, src, w, Y, class_w)


@functools.lru_cache(maxsize=16)
def _program(K: int, n: int, mesh: Mesh, tile_n: int, interpret):
    """`gee_packed`'s shard_map-ped body under `jax.jit`, built once
    per key."""
    body = functools.partial(_body_reduce_scatter, K=K, n=n,
                             tile_n=tile_n, interpret=interpret)
    return jax.jit(shard_map(body, mesh,
                             in_specs=(P(AXIS),) * 3 + (P(), P()),
                             out_specs=P(AXIS, None)))


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


def collective_bytes(n_pad: int, K: int, p: int) -> int:
    """The least bytes one chip must send for one embed's collective, a
    reduce-scatter of the (n_pad, K) f32 accumulator: (p-1)/p of it,
    from static shapes.  The collective XLA lowers may send more: on
    TPU v5e `psum_scatter` lowers to an all-reduce and a slice, twice
    the reduce-scatter's bytes, so a bandwidth share taken from this
    count is a share of the minimum."""
    return (p - 1) * n_pad * K * 4 // p


def gee_distributed(graph, Y, *, K: int, mesh: Optional[Mesh] = None,
                    laplacian: bool = False):
    """Host-friendly wrapper: pads edges (at least one a chip) and rows
    to the mesh, packs the edges on the chips (`pack_sharded`), embeds
    (`gee_packed`) and unpads.  Returns Z (n, K) as numpy."""
    mesh = mesh or edge_mesh()
    p = mesh.shape[AXIS]
    n_pad = pad_rows(graph.n, p)
    g = graph.pad_to(pad_rows(max(graph.s, 1), p))
    Y_pad = np.full(n_pad, -1, np.int32)
    Y_pad[:graph.n] = Y
    Yj = jnp.asarray(Y_pad)
    packed = pack_sharded(jnp.asarray(g.u), jnp.asarray(g.v),
                          jnp.asarray(g.w), n=n_pad, mesh=mesh,
                          laplacian=laplacian)
    Z = gee_packed(*packed, Yj, class_weights(Yj, K), K=K, n=n_pad,
                   mesh=mesh)
    return np.asarray(Z)[:graph.n]
