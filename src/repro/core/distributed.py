"""Distributed GEE: the paper's shared-memory edge-parallelism mapped to
SPMD collectives.

The paper's Ligra implementation parallelizes the edge loop across cores
that share one coherent DRAM array Z, racing on Z[u, k] and resolving
races with lock-free atomic adds.  On a TPU pod there is no shared
mutable HBM, so "who owns Z" becomes an explicit design axis.  Four
reduction modes, all computing Z to float32 rounding:

  replicated      every chip: local XLA scatter-add into a full (n, K)
                  Z, then all-reduce (psum).  Direct analog of the
                  paper's shared array.  Memory O(n*K) per chip.
  reduce_scatter  every chip runs the destination-tiled scatter kernel
                  (`kernels.gee_scatter`) over its own contributions,
                  packed once on the chips (`pack_sharded`), into a full
                  (n, K) Z; psum_scatter leaves each chip its own row
                  shard, whose columns it scales by the class weights.
                  Memory O(n*K) transient, O(n*K/P) resident;
                  collective cost = 1 reduce-scatter.
  a2a             contributions bucketed by destination row-shard
                  (sort + capacity-padded pack, exactly like an MoE
                  dispatch), exchanged with one all_to_all, then local
                  scatter into the (n/P, K) shard.  Memory O(s/P).
  ring            the same buckets forwarded around the ring with
                  collective_permute (ICI-neighbor traffic only), each
                  chip folding in its bucket as the accumulator passes.
                  P-1 steps; peak memory O(n*K/P + s/P); this is the
                  TPU-native replacement for atomics: deterministic
                  neighbor exchanges instead of racing writes.

Each mode's embed is one jitted program, built once per key and cached
(`_program`, `_packed_program`), so a refit dispatches one executable.
The edges (or, in reduce_scatter, the packed slots) arrive sharded over
the mesh's edge axis; the labels and the (K,) class weights 1/n_k
arrive replicated.  replicated, a2a and ring weight each contribution
by its donor's class weight, read by the donor's label; reduce_scatter
scatters the edge weight under the donor's class and scales column k
by 1/n_k once, after the reduction.

Bucketed modes use capacity padding (cap = mean * capacity_factor).
With randomly-shuffled edges, bucket sizes concentrate tightly around
the mean; overflow is *counted and returned* so callers can assert
drops == 0 (tests do) or re-run with a higher factor.
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
#: modes that scatter every contribution, with no capacity padding
SCATTER_MODES = ("replicated", "reduce_scatter")


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
# in-shard helpers
# ---------------------------------------------------------------------------


def _bucket_by_owner(dst, cls, val, rows: int, p: int, cap: int):
    """Pack contributions into (p, cap) per-owner buckets (sort + pad).

    Returns (b_row, b_cls, b_val, dropped).  b_row holds owner-local row
    indices; padded slots have val 0."""
    owner = dst // rows
    order = jnp.argsort(owner)
    owner_s = owner[order]
    row_s = (dst - owner * rows)[order]
    cls_s = cls[order]
    val_s = val[order]

    starts = jnp.searchsorted(owner_s, jnp.arange(p))
    pos = jnp.arange(owner.shape[0]) - starts[owner_s]
    keep = pos < cap
    slot = jnp.where(keep, owner_s * cap + pos, p * cap)

    def pack(x, fill):
        buf = jnp.full((p * cap + 1,), fill, x.dtype).at[slot].set(x)
        return buf[:-1].reshape(p, cap)

    b_row = pack(row_s, jnp.int32(0))
    b_cls = pack(cls_s, jnp.int32(0))
    b_val = pack(jnp.where(keep, val_s, 0.0), jnp.float32(0))
    dropped = jnp.sum(~keep)
    return b_row, b_cls, b_val, dropped


def _contributions(u, v, w, Y, class_w):
    """Per-directed-edge (dst, class, value) pairs, both directions, as
    `core.gee.edge_contributions` gives them, with each donor's weight
    read from the (K,) class weights by its label: the value
    `make_w(Y, K, class_w)` holds for it, bit for bit.  On the TPU one
    jitted program prefetches one gather table into fast memory (the
    labels); gathering a second (n,) table, the per-node weights, would
    read HBM for every contribution."""
    yv, yu = Y[v], Y[u]
    cv, cu = jnp.maximum(yv, 0), jnp.maximum(yu, 0)
    dst = jnp.concatenate([u, v])
    cls = jnp.concatenate([cv, cu])
    val = jnp.concatenate([jnp.where(yv >= 0, class_w[cv] * w, 0.0),
                           jnp.where(yu >= 0, class_w[cu] * w, 0.0)])
    return dst, cls, val


def _scatter_rows(rows: int, K: int, r, c, v):
    return jnp.zeros((rows, K), jnp.float32).at[r, c].add(v)


# ---------------------------------------------------------------------------
# shard_map bodies
# ---------------------------------------------------------------------------


def _body_replicated(u, v, w, Y, class_w, *, K, n):
    dst, cls, val = _contributions(u, v, w, Y, class_w)
    Z = _scatter_rows(n, K, dst, cls, val)
    return jax.lax.psum(Z, AXIS), jnp.zeros((), jnp.int32)


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


def _body_a2a(u, v, w, Y, class_w, *, K, n, p, cap):
    rows = n // p
    dst, cls, val = _contributions(u, v, w, Y, class_w)
    b_row, b_cls, b_val, dropped = _bucket_by_owner(dst, cls, val, rows, p,
                                                    cap)
    r = jax.lax.all_to_all(b_row, AXIS, split_axis=0, concat_axis=0,
                           tiled=False)
    c = jax.lax.all_to_all(b_cls, AXIS, split_axis=0, concat_axis=0,
                           tiled=False)
    x = jax.lax.all_to_all(b_val, AXIS, split_axis=0, concat_axis=0,
                           tiled=False)
    Z = _scatter_rows(rows, K, r.reshape(-1), c.reshape(-1), x.reshape(-1))
    return Z, jax.lax.psum(dropped, AXIS)


def _body_a2a_prebucketed(b_dst, b_cls, b_wv, Y, class_w, *, K, n, p):
    """Steady-state a2a: buckets were built once at ingestion (the owner
    of a contribution depends only on the destination node, not on the
    labels), so refinement iterations skip the sort entirely.  b_* are
    (p, cap) per-owner buckets of (local_row, class-source node, weight).
    Class/value are resolved per iteration from the CURRENT labels."""
    y = Y[b_cls]
    cls = jnp.maximum(y, 0)
    val = jnp.where(y >= 0, class_w[cls] * b_wv, 0.0)
    r = jax.lax.all_to_all(b_dst, AXIS, split_axis=0, concat_axis=0)
    c = jax.lax.all_to_all(cls, AXIS, split_axis=0, concat_axis=0)
    x = jax.lax.all_to_all(val, AXIS, split_axis=0, concat_axis=0)
    rows = n // p
    Z = _scatter_rows(rows, K, r.reshape(-1), c.reshape(-1), x.reshape(-1))
    return Z, jnp.zeros((), jnp.int32)


def prebucket_host(graph, p: int, capacity_factor=None):
    """One-time ingestion pass: route every directed contribution to its
    destination's row-owner bucket.  Returns (b_dst_local, b_srcnode,
    b_weight) arrays of shape (p_shards, p_owners, cap) — give shard i
    its [i] slice.  The class/value resolution stays per-iteration."""
    if capacity_factor is None:
        capacity_factor = exact_capacity_factor(graph, p)
    n_pad = pad_rows(graph.n, p)
    s_pad = pad_rows(graph.s, p)
    g = graph.pad_to(s_pad)
    rows = n_pad // p
    per = s_pad // p
    cap = int(np.ceil(2 * per / p * capacity_factor)) + 8
    b_dst = np.zeros((p, p, cap), np.int32)
    b_src = np.zeros((p, p, cap), np.int32)
    b_w = np.zeros((p, p, cap), np.float32)
    for shard in range(p):
        sl = slice(shard * per, (shard + 1) * per)
        dst = np.concatenate([g.u[sl], g.v[sl]])
        src = np.concatenate([g.v[sl], g.u[sl]])   # label donor
        w = np.concatenate([g.w[sl], g.w[sl]])
        owner = dst // rows
        order = np.argsort(owner, kind="stable")
        dst, src, w, owner = dst[order], src[order], w[order], owner[order]
        starts = np.searchsorted(owner, np.arange(p))
        pos = np.arange(dst.shape[0]) - starts[owner]
        keep = pos < cap
        b_dst[shard, owner[keep], pos[keep]] = dst[keep] - owner[keep] * rows
        b_src[shard, owner[keep], pos[keep]] = src[keep]
        b_w[shard, owner[keep], pos[keep]] = w[keep]
        assert keep.all(), "prebucket overflow; raise capacity_factor"
    return b_dst, b_src, b_w, n_pad


def gee_a2a_steady(b_dst, b_src, b_w, Y, *, K: int, n_pad: int, mesh: Mesh):
    """Per-iteration embed with pre-bucketed contributions (no sort).

    b_* are the (p, p, cap) host buckets flattened to (p*p, cap) so the
    leading dim shards p-ways (each shard gets its (p, cap) slab)."""
    return _steady_program(K, n_pad, mesh)(b_dst, b_src, b_w, Y)


@functools.lru_cache(maxsize=16)
def _steady_program(K: int, n_pad: int, mesh: Mesh):
    """`gee_a2a_steady`'s jitted program, built once per (K, n_pad,
    mesh)."""
    body = functools.partial(_body_a2a_prebucketed, K=K, n=n_pad,
                             p=mesh.shape[AXIS])
    fn = shard_map(body, mesh,
                   in_specs=(P(AXIS), P(AXIS), P(AXIS), P(), P()),
                   out_specs=(P(AXIS, None), P()))

    def run(b_dst, b_src, b_w, Y):
        return fn(b_dst, b_src, b_w, Y, class_weights(Y, K))
    return jax.jit(run)


def _body_ring(u, v, w, Y, class_w, *, K, n, p, cap):
    rows = n // p
    me = jax.lax.axis_index(AXIS)
    dst, cls, val = _contributions(u, v, w, Y, class_w)
    b_row, b_cls, b_val, dropped = _bucket_by_owner(dst, cls, val, rows, p,
                                                    cap)

    def bucket_dense(c):
        r = jax.lax.dynamic_index_in_dim(b_row, c, 0, keepdims=False)
        k = jax.lax.dynamic_index_in_dim(b_cls, c, 0, keepdims=False)
        x = jax.lax.dynamic_index_in_dim(b_val, c, 0, keepdims=False)
        return _scatter_rows(rows, K, r, k, x)

    perm = [(i, (i - 1) % p) for i in range(p)]
    acc = bucket_dense((me + 1) % p)

    def step(t, acc):
        acc = jax.lax.ppermute(acc, AXIS, perm)
        return acc + bucket_dense((me + t + 1) % p)

    acc = jax.lax.fori_loop(1, p, step, acc)
    return acc, jax.lax.psum(dropped, AXIS)


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
    return _packed_program(K, n, mesh, tile_n, interpret)(
        rows, src, w, Y, class_w)


@functools.lru_cache(maxsize=16)
def _packed_program(K: int, n: int, mesh: Mesh, tile_n: int, interpret):
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


def bucket_cap(mode: str, s_local: int, p: int,
               capacity_factor: float) -> int:
    """Slots of one (shard, owner) bucket in the bucketed modes: the
    mean 2 * s_local / p contributions times the capacity factor, + 8;
    0 in the scatter modes, which bucket nothing."""
    if mode in SCATTER_MODES:
        return 0
    return int(np.ceil(2 * s_local / p * capacity_factor)) + 8


def embed_slots(mode: str, s_pad: int, p: int, cap: int) -> int:
    """Contribution slots all chips scatter in one embed, padding
    included, in the modes that scatter with XLA: the 2 * s_pad
    contributions of the padded edges in replicated, p * p buckets of
    `cap` in the bucketed ones.  (reduce_scatter runs the kernel over
    its packed slots, whose count is their size.)"""
    return {"replicated": 2 * s_pad, "a2a": p * p * cap,
            "ring": p * p * cap}[mode]


def collective_bytes(mode: str, n_pad: int, K: int, p: int, cap: int
                     ) -> int:
    """The least bytes one chip must send for one embed's collective,
    as the algorithm states it, from static shapes (f32 Z, int32/f32
    buckets; the drop count's scalar psum is left out).  The collective
    XLA lowers may send more: on TPU v5e `psum_scatter` of the (n_pad,
    K) accumulator lowers to an all-reduce and a slice, twice the
    reduce-scatter's bytes, so a bandwidth share taken from this count
    is a share of the minimum:

      replicated      all-reduce of (n_pad, K): 2 (p-1)/p * n_pad*K*4
      reduce_scatter  reduce-scatter of (n_pad, K): (p-1)/p * n_pad*K*4
      a2a             three all_to_alls of (p, cap) int32/int32/f32
                      buckets, all but its own: (p-1) * cap * 12
      ring            p-1 collective_permutes of the (n_pad/p, K)
                      accumulator: (p-1) * n_pad/p * K*4
    """
    z = n_pad * K * 4
    return {"replicated": 2 * (p - 1) * z // p,
            "reduce_scatter": (p - 1) * z // p,
            "a2a": (p - 1) * cap * 12,
            "ring": (p - 1) * z // p}[mode]


def gee_sharded(u, v, w, Y, class_w, *, K: int, n: int, mesh: Mesh,
                mode: str = "ring", capacity_factor: float = 2.0,
                laplacian: bool = False):
    """Distributed GEE under shard_map, one jitted program per (mode,
    K, n, mesh, bucket capacity, laplacian) (`_program`); reduce_scatter
    packs the edges on the chips first (`pack_sharded`, with one read
    back) and runs `gee_packed`.

    u, v, w: (s,) edge arrays, s divisible by mesh size (pad first —
    `Graph.pad_to`); sharded over the mesh's edge axis, each chip holds
    its s/p.  Y: (n_pad,) labels, n divisible by mesh size for
    row-sharded modes; class_w: (K,) class weights 1/n_k of Y
    (`core.gee.class_weights`): class k's contributions weigh 1/n_k.
    Returns (Z, dropped):
      replicated          -> Z (n, K) replicated
      others              -> Z (n, K) row-sharded over the mesh
    """
    p = mesh.shape[AXIS]
    assert u.shape[0] % p == 0, (u.shape, p)
    if mode not in SCATTER_MODES + ("a2a", "ring"):
        raise ValueError(f"unknown mode {mode!r}")
    if mode != "replicated":
        assert n % p == 0, (n, p)
    if mode == "reduce_scatter":
        packed = pack_sharded(u, v, w, n=n, mesh=mesh, laplacian=laplacian)
        return (gee_packed(*packed, Y, class_w, K=K, n=n, mesh=mesh),
                jnp.zeros((), jnp.int32))
    cap = bucket_cap(mode, u.shape[0] // p, p, capacity_factor)
    return _program(mode, K, n, mesh, cap, laplacian)(u, v, w, Y, class_w)


@functools.lru_cache(maxsize=16)
def _program(mode: str, K: int, n: int, mesh: Mesh, cap: int,
             laplacian: bool):
    """`gee_sharded`'s shard_map-ped body under `jax.jit`, built once
    per key: every later embed of the same shapes dispatches the one
    cached executable."""
    p = mesh.shape[AXIS]
    body, out_z = {
        "replicated": (functools.partial(_body_replicated, K=K, n=n),
                       P()),
        "a2a": (functools.partial(_body_a2a, K=K, n=n, p=p, cap=cap),
                P(AXIS, None)),
        "ring": (functools.partial(_body_ring, K=K, n=n, p=p, cap=cap),
                 P(AXIS, None)),
    }[mode]
    fn = shard_map(body, mesh,
                   in_specs=(P(AXIS), P(AXIS), P(AXIS), P(), P()),
                   out_specs=(out_z, P()))

    def run(u, v, w, Y, class_w):
        return fn(u, v, _edge_weights(u, v, w, n, laplacian), Y, class_w)
    return jax.jit(run)


def exact_capacity_factor(graph, p: int) -> float:
    """Capacity factor guaranteeing zero drops: measured from the actual
    per-(shard, owner) bucket histogram.  O(s) host pass.  This is the
    skew-robust answer to what Ligra got from work stealing: supernodes
    (power-law hubs) concentrate contributions on one row-owner, which a
    mean-sized bucket cannot hold."""
    from repro.graph.partition import owner_histogram
    hist = owner_histogram(graph, p)
    s_pad = pad_rows(graph.s, p)
    mean_bucket = max(2 * (s_pad // p) / p, 1.0)
    return float(hist.max()) / mean_bucket + 0.05


def gee_distributed(graph, Y, *, K: int, mode: str = "ring",
                    mesh: Optional[Mesh] = None,
                    capacity_factor=None,
                    laplacian: bool = False):
    """Host-friendly wrapper: pads edges/rows, runs, unpads.

    capacity_factor None -> exact (zero-drop) factor measured from the
    graph's owner histogram.  Returns (Z (n, K), dropped count)."""
    mesh = mesh or edge_mesh()
    p = mesh.shape[AXIS]
    if capacity_factor is None:
        capacity_factor = exact_capacity_factor(graph, p)
    n_pad = pad_rows(graph.n, p)
    s_pad = pad_rows(graph.s, p)
    g = graph.pad_to(s_pad)
    Y_pad = np.full(n_pad, -1, np.int32)
    Y_pad[:graph.n] = Y
    Yj = jnp.asarray(Y_pad)
    Z, dropped = gee_sharded(
        jnp.asarray(g.u), jnp.asarray(g.v), jnp.asarray(g.w),
        Yj, class_weights(Yj, K), K=K, n=n_pad, mesh=mesh, mode=mode,
        capacity_factor=capacity_factor, laplacian=laplacian)
    return np.asarray(Z)[:graph.n], int(dropped)
