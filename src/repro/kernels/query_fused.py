"""Fused query-side Pallas kernels for the serving read/write hot path.

Two kernels, both blocked over the shard's owned slice:

``topk_fused``
    normalize + cosine score + running top-k merge in ONE pallas_call,
    replacing the separate ``normalize_rows`` pass and per-block jitted
    ``_topk_block`` calls of `repro.serving.queries`.  The grid walks
    candidate blocks in ascending-global-id order while the running
    (vals, idxs) top-k stays resident in the revisited output block, so
    the whole scan is a single dispatch with no intermediate Zn
    materialization (``normalize=True`` additionally emits the
    normalized slice so a caller can populate its Zn cache from the
    same pass).

``gee_delta_renorm``
    delta-apply + renormalize in ONE pallas_call for the
    partial_fit-then-query serving turnaround: the grid is the same
    destination-tiled (T, BPT) layout as `gee_scatter`, each Z tile is
    loaded once, accumulated over its packed contribution blocks, and
    row-normalized on the final visit — Z never makes a second
    HBM round trip between the write and the read path.

**Bit-equality contract.**  Where its blocks are the scan's (at most
QUERY_BLOCK queries, a bucket of at most MAX_BLOCK_ROWS rows),
``topk_fused`` reproduces the `repro.serving.queries` blocked scan
bit-exactly in interpret mode (tested with ``np.array_equal``, not
allclose): per-block scores come from the one shared `cosine_scores`,
the block merge selects exactly what ``lax.top_k`` over
running-BEFORE-block would (k rounds of max, lowest position of the
max, mask — Mosaic has no top_k), blocks are presented in
ascending-global-id order, and normalization reduces over exactly K
columns (the candidate block's second dim is K, never the lane-padded
kdim), so score ties resolve to the ascending global id exactly as the
unfused path does.  Larger inputs are walked in smaller blocks, which
can move a score by an ulp (another matmul shape), and the compiled
kernel's matmul is Mosaic's, not XLA's: there the answers agree
tie-tolerantly (`tests/conftest.py:topk_equivalent`).
``interpret="auto"`` resolves per platform like every other kernel
here.
"""
from __future__ import annotations

import functools
from typing import Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.gee_scatter import (accumulate_block, edge_block_spec,
                                       resolve_interpret)

EPS = 1e-9      # normalize_rows' clamp — must match queries.normalize_rows
#: top-k kernel block bounds: candidate rows and queries per grid step
MAX_BLOCK_ROWS = 2048
QUERY_BLOCK = 64
_BIG = jnp.iinfo(jnp.int32).max
_NEG_ID = jnp.iinfo(jnp.int32).min


def _normalize(z, eps):
    return z / jnp.maximum(jnp.linalg.norm(z, axis=-1, keepdims=True), eps)


def cosine_scores(q, z):
    """(nq, K) x (m, K) -> (nq, m) scores at f32 precision (the MXU
    default would round both operands to bf16).  THE one copy shared
    by the fused kernel and the jitted blocked scan, so their scores
    stay bit-identical."""
    return jax.lax.dot_general(q, z, (((1,), (1,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _take_first_max(v, alive, pos):
    """Max of the alive entries of each row of `v`, and the lowest
    position holding it (the `lax.top_k` tie rule)."""
    vm = jnp.where(alive, v, -jnp.inf)
    best = jnp.max(vm, axis=1, keepdims=True)
    at = jnp.min(jnp.where(alive & (vm == best), pos, _BIG), axis=1,
                 keepdims=True)
    return best, at


def _merge_topk(run_v, run_i, scores, gidx, k):
    """`lax.top_k(concat([run_v, scores]), k)` with the running list
    first, as k rounds of max -> lowest position of the max -> mask:
    only elementwise ops and lane reductions, which Mosaic lowers (it
    has no top_k, and a lane-axis concatenate of unaligned widths is
    refused).  Ties between the two sides go to the running list,
    whose ids are all lower than the block's."""
    nq, bucket = scores.shape
    rpos = jax.lax.broadcasted_iota(jnp.int32, (nq, k), 1)
    bpos = jax.lax.broadcasted_iota(jnp.int32, (nq, bucket), 1)
    gid = jnp.broadcast_to(gidx, (nq, bucket))
    r_alive = jnp.ones((nq, k), jnp.bool_)
    b_alive = jnp.ones((nq, bucket), jnp.bool_)
    out_v = jnp.full((nq, k), -jnp.inf, jnp.float32)
    out_i = jnp.full((nq, k), -1, jnp.int32)
    for j in range(k):
        rv, rat = _take_first_max(run_v, r_alive, rpos)
        bv, bat = _take_first_max(scores, b_alive, bpos)
        from_run = rv >= bv                               # (nq, 1)
        r_hit = from_run & (rpos == rat)
        b_hit = ~from_run & (bpos == bat)
        rid = jnp.max(jnp.where(r_hit, run_i, _NEG_ID), axis=1,
                      keepdims=True)
        bid = jnp.max(jnp.where(b_hit, gid, _NEG_ID), axis=1,
                      keepdims=True)
        out_v = jnp.where(rpos == j, jnp.where(from_run, rv, bv), out_v)
        out_i = jnp.where(rpos == j, jnp.where(from_run, rid, bid), out_i)
        r_alive = r_alive & ~r_hit
        b_alive = b_alive & ~b_hit
    return out_v, out_i


def _topk_kernel(z_ref, q_ref, qn_ref, vals_ref, idxs_ref, *rest,
                 rows: int, qblock: int, k: int, m: int, row_offset: int,
                 exclude_self: bool, normalize: bool, eps: float):
    b = pl.program_id(0)
    qs = pl.ds(pl.multiple_of(pl.program_id(1) * qblock, qblock), qblock)

    @pl.when(b == 0)
    def _init():
        vals_ref[qs, :] = jnp.full((qblock, k), -jnp.inf, jnp.float32)
        idxs_ref[qs, :] = jnp.full((qblock, k), -1, jnp.int32)

    z = z_ref[...]                                        # (rows, K)
    if normalize:
        z = _normalize(z, eps)
        rest[0][...] = z                                  # zn_ref
    local = b * rows + jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
    gidx = jnp.where(local < m, row_offset + local, -1)   # -1: padding
    scores = cosine_scores(q_ref[qs, :], z)               # (qblock, rows)
    mask = gidx < 0
    if exclude_self:
        mask = mask | (gidx == qn_ref[qs, :])
    scores = jnp.where(mask, -jnp.inf, scores)
    # running candidates BEFORE the block: ties resolve to the lower
    # (earlier, ascending) global id, as lax.top_k's position rule does
    v, i = _merge_topk(vals_ref[qs, :], idxs_ref[qs, :], scores, gidx, k)
    vals_ref[qs, :] = v
    idxs_ref[qs, :] = i


@functools.partial(jax.jit, static_argnames=(
    "k", "bucket", "row_offset", "exclude_self", "normalize", "eps",
    "interpret"))
def topk_fused(Z_rows, q, qnodes, *, k: int, bucket: int,
               row_offset: int = 0, exclude_self: bool = True,
               normalize: bool = False, eps: float = EPS,
               interpret: Union[bool, str] = "auto"):
    """Blocked normalize+cosine+top-k in one pallas_call.

    Z_rows (m, K): candidate rows at global ids [row_offset,
    row_offset + m) — RAW when normalize=True, unit-norm otherwise.
    q (nq, K) unit-norm queries; qnodes (nq,) global ids for
    self-exclusion.  `bucket` is the caller's static block size
    (`queries._topk_blocked`'s bucket rule); the kernel walks it in
    blocks of at most MAX_BLOCK_ROWS rows and QUERY_BLOCK queries, so
    its VMEM working set stays bounded (a (rows, K) f32 block is
    lane-padded to 128 on the TPU).  The merge keeps the (-score,
    ascending id) order whatever the blocking.

    Returns (vals (nq, k) f32, idxs (nq, k) i32) device arrays — plus
    Zn (m, K) when normalize=True.  Unfilled slots are NOT clamped here
    (the queries-layer wrapper applies the shared isfinite -> -1 pass).
    """
    interpret = resolve_interpret(interpret)
    m, K = Z_rows.shape
    nq = q.shape[0]
    rows = min(bucket, MAX_BLOCK_ROWS)
    mp = max(-(-m // rows) * rows, rows)
    Zp = jnp.asarray(Z_rows)
    if mp != m:
        Zp = jnp.pad(Zp, ((0, mp - m), (0, 0)))
    qblock = min(-(-nq // 8) * 8, QUERY_BLOCK)
    nqp = -(-nq // qblock) * qblock
    qp = jnp.asarray(q)
    qn = jnp.asarray(qnodes, jnp.int32).reshape(nq, 1)
    if nqp != nq:
        qp = jnp.pad(qp, ((0, nqp - nq), (0, 0)))
        qn = jnp.pad(qn, ((0, nqp - nq), (0, 0)), constant_values=-1)
    z_spec = pl.BlockSpec((rows, K), lambda b, j: (b, 0))
    q_spec = pl.BlockSpec((nqp, K), lambda b, j: (0, 0))
    qn_spec = pl.BlockSpec((nqp, 1), lambda b, j: (0, 0))
    run_spec = pl.BlockSpec((nqp, k), lambda b, j: (0, 0))  # resident
    out_specs = [run_spec, run_spec]
    out_shape = [jax.ShapeDtypeStruct((nqp, k), jnp.float32),
                 jax.ShapeDtypeStruct((nqp, k), jnp.int32)]
    if normalize:
        out_specs.append(z_spec)
        out_shape.append(jax.ShapeDtypeStruct((mp, K), jnp.float32))
    out = pl.pallas_call(
        functools.partial(_topk_kernel, rows=rows, qblock=qblock, k=k,
                          m=m, row_offset=row_offset,
                          exclude_self=exclude_self,
                          normalize=normalize, eps=eps),
        grid=(mp // rows, nqp // qblock),
        in_specs=[z_spec, q_spec, qn_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="topk_fused",
    )(Zp, qp, qn)
    vals, idxs = out[0][:nq], out[1][:nq]
    if normalize:
        return vals, idxs, out[2][:m]
    return vals, idxs


def _delta_kernel(rows_ref, cls_ref, val_ref, z_ref, znew_ref, zn_ref, *,
                  tile_n: int, kdim: int, bpt: int, eps: float):
    b = pl.program_id(1)

    @pl.when(b == 0)
    def _init():
        znew_ref[...] = z_ref[...]

    znew_ref[...] += accumulate_block(
        rows_ref[...], cls_ref[...], val_ref[...].astype(jnp.float32),
        tile_n=tile_n, kdim=kdim)

    @pl.when(b == bpt - 1)
    def _renorm():
        zn_ref[...] = _normalize(znew_ref[...], eps)


@functools.partial(jax.jit, static_argnames=("tile_n", "eps",
                                             "interpret"))
def gee_delta_renorm(Z, rows, cls, val, *, tile_n: int, eps: float = EPS,
                     interpret: Union[bool, str] = "auto"):
    """Fold packed delta contributions into Z and renormalize — one
    pallas_call, one Z round trip.

    Z (n_local, K) float32; rows/cls/val (T, BPT, 1, EB) packed blocks
    over local destination rows (see ops.pack_edges — padded slots
    carry val = 0 and are no-ops).  The second dim stays K (no lane
    padding) so the row norm reduces over exactly the K real columns,
    matching queries.normalize_rows bit-for-bit.

    Returns (Z_new (n_local, K), Zn (n_local, K)) device arrays.
    """
    interpret = resolve_interpret(interpret)
    T, BPT, _, EB = rows.shape
    n_local, K = Z.shape
    Zp = jnp.asarray(Z, jnp.float32)
    if T * tile_n != n_local:
        Zp = jnp.pad(Zp, ((0, T * tile_n - n_local), (0, 0)))
    eb_spec = edge_block_spec(EB)
    z_spec = pl.BlockSpec((tile_n, K), lambda t, b: (t, 0))
    znew, zn = pl.pallas_call(
        functools.partial(_delta_kernel, tile_n=tile_n, kdim=K,
                          bpt=BPT, eps=eps),
        grid=(T, BPT),
        in_specs=[eb_spec, eb_spec, eb_spec, z_spec],
        out_specs=[z_spec, z_spec],
        out_shape=[jax.ShapeDtypeStruct((T * tile_n, K), jnp.float32),
                   jax.ShapeDtypeStruct((T * tile_n, K), jnp.float32)],
        interpret=interpret,
        name="gee_delta_renorm",
    )(jnp.asarray(rows), jnp.asarray(cls), jnp.asarray(val), Zp)
    return znew[:n_local], zn[:n_local]
