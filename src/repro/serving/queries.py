"""Jitted query kernels over a served embedding Z (n, K).

Three read paths, each shaped for microbatching (`batcher.py` stacks
many user requests into one kernel call):

* ``gather_embeddings``  — Z rows for a node batch.
* ``predict_labels``     — nearest-class-centroid label prediction in
  cosine space (centroids from the epoch's labeled nodes).
* ``topk_cosine``        — blocked top-k cosine nearest neighbors over
  all n rows; the candidate matrix is processed ``block_rows`` rows at
  a time so peak memory is O(q · block_rows), not O(q · n), and the
  running top-k is merged with ``lax.top_k`` per block.

Every kernel also exists in a **row-sliced** form for the sharded
engine, where no process holds all of Z (with owned-rows encoder plans
a shard's `Z_owned` IS its whole accumulator — these kernels only ever
see the (n/p, K) local slice plus its global `row_offset`):

* ``topk_cosine_q``      — top-k of externally supplied query vectors
  against a candidate row block living at ``row_offset`` in the global
  index space (a shard's owned slice); per-shard results merge exactly
  because scores are global-id-stamped.
* ``topk_cosine_ids``    — same, but for **gathered** candidate rows
  with explicit (ascending) global ids — the IVF index's per-cell
  scorer (`repro.index`), where a cell's rows are not contiguous.
* ``class_sums``         — per-class (sums, counts) over a row slice;
  the engine reduces slices and divides once, so merged centroids
  equal the single-host ``class_centroids``.
* ``predict_rows``       — centroid prediction from gathered rows
  (the engine gathers rows from owning shards first).

**Tie-breaking contract (bit-stable results).**  Every top-k surface
here orders candidates lexicographically by ``(-score, ascending
global id)``.  Inside the blocked scans this falls out of two
invariants rather than an explicit composite sort: ``lax.top_k``
breaks value ties in favor of the lower input position, and candidates
are always presented in ascending-global-id order (blocks scan rows in
id order; the running top-k — itself tie-ordered by id, inductively —
is concatenated *before* the new block, whose ids are all larger).
``merge_topk`` gets parts whose id ranges interleave (shards, IVF
cells), so it sorts explicitly and is order-invariant in its inputs.
The payoff: sharded, single-host, and IVF answers are **bit-identical**
(not merely tie-tolerant), which is what lets the IVF index be tested
for exact equality against the full scan at ``nprobe=K``.

Kernels are pure functions of (Z, ...) so they jit once per shape and
stay valid across versions/epochs — the service just passes its
current Z.
"""
from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.query_fused import cosine_scores, topk_fused


@jax.jit
def gather_embeddings(Z, nodes):
    return Z[nodes]


def normalize_rows(X, eps=1e-9):
    return X / jnp.maximum(jnp.linalg.norm(X, axis=-1, keepdims=True), eps)


@functools.partial(jax.jit, static_argnames=("K",))
def class_sums(Z_rows, Y_rows, *, K: int):
    """Per-class (sums (K, K), counts (K,)) over a row slice — the
    shard-local half of `class_centroids`; sum across shards and divide
    once to get the global centroids."""
    labeled = (Y_rows >= 0).astype(Z_rows.dtype)
    onehot = jax.nn.one_hot(jnp.maximum(Y_rows, 0), K, dtype=Z_rows.dtype)
    onehot = onehot * labeled[:, None]
    return onehot.T @ Z_rows, onehot.sum(0)


@functools.partial(jax.jit, static_argnames=("K",))
def class_centroids(Z, Y, *, K: int):
    """Mean embedding of each class's labeled nodes (K, K-dim).  THE
    one copy of the masking/one-hot math is `class_sums`, so the
    sharded merge (sum partials, divide once) cannot drift from the
    single-host answer."""
    sums, counts = class_sums(Z, Y, K=K)
    return sums / jnp.maximum(counts[:, None], 1.0)


@jax.jit
def predict_rows(rows, centroids):
    """Label = argmax cosine(row, centroid_k) for already-gathered rows.
    Returns (pred, score)."""
    q = normalize_rows(rows)
    c = normalize_rows(centroids)
    sims = cosine_scores(q, c)
    return jnp.argmax(sims, 1).astype(jnp.int32), jnp.max(sims, 1)


@jax.jit
def predict_labels(Z, centroids, nodes):
    """Label = argmax cosine(Z[node], centroid_k).  Returns (pred, score)."""
    return predict_rows(Z[nodes], centroids)


@functools.partial(jax.jit, static_argnames=("k", "exclude_self"))
def _topk_block(vals, idxs, q, block, gidx, qnodes, *,
                exclude_self: bool, k: int):
    """Merge one candidate block into the running (vals, idxs) top-k.

    `gidx` carries each block row's global id (-1 for padding rows,
    which are masked out).  The running candidates are concatenated
    BEFORE the block: with blocks presented in ascending-id order and
    ``lax.top_k``'s lower-position-wins tie rule, score ties resolve to
    the ascending global id (see the module tie-breaking contract)."""
    scores = cosine_scores(q, block)                       # (q, B)
    mask = gidx[None, :] < 0                               # padding rows
    if exclude_self:
        mask = mask | (gidx[None, :] == qnodes[:, None])
    scores = jnp.where(mask, -jnp.inf, scores)
    cat_v = jnp.concatenate([vals, scores], 1)
    cat_i = jnp.concatenate(
        [idxs, jnp.broadcast_to(gidx, scores.shape)], 1)
    v, sel = jax.lax.top_k(cat_v, k)
    return v, jnp.take_along_axis(cat_i, sel, 1)


#: reusable per-thread host buffer for the blocked scan's padded tail
#: block ids — the tail is rebuilt every scan but its shape recurs, so
#: the scan fills one buffer in place instead of allocating a fresh
#: np.concatenate result per call (the jnp conversion at the call site
#: copies, so reuse can never alias a pending device computation)
_TAIL = threading.local()


def _padded_tail(gidx: np.ndarray, bucket: int) -> np.ndarray:
    buf = getattr(_TAIL, "buf", None)
    if buf is None or buf.shape[0] != bucket:
        buf = np.empty(bucket, np.int32)
        _TAIL.buf = buf
    t = gidx.shape[0]
    buf[:t] = gidx
    buf[t:] = -1
    return buf


def _bucket_rows(m: int, block_rows: int) -> int:
    """The blocked scan's static block size: single-block inputs pad to
    a power-of-two bucket (one compile per bucket for the IVF path's
    varying cell sizes); multi-block scans use the fixed block shape
    and pad only the tail."""
    return block_rows if m > block_rows else \
        min(block_rows, _pow2(max(m, 1)))


def _topk_blocked(Zn_rows, ids, q, qnodes, *, k: int, block_rows: int,
                  exclude_self: bool):
    """Shared blocked scan: score `q` against candidate rows carrying
    global ids `ids` (ascending), k+block at a time."""
    m = Zn_rows.shape[0]
    qnodes = jnp.asarray(np.asarray(qnodes, np.int32))
    nq = q.shape[0]
    vals = jnp.full((nq, k), -jnp.inf, Zn_rows.dtype)
    idxs = jnp.full((nq, k), -1, jnp.int32)
    bucket = _bucket_rows(m, block_rows)
    for base in range(0, max(m, 1), bucket):
        block = Zn_rows[base:min(base + bucket, m)]
        gidx = ids[base:min(base + bucket, m)]
        if block.shape[0] < bucket:
            block = jnp.pad(block, ((0, bucket - block.shape[0]),
                                    (0, 0)))
            gidx = _padded_tail(gidx, bucket)
        vals, idxs = _topk_block(vals, idxs, q, block,
                                 jnp.array(gidx), qnodes,
                                 exclude_self=exclude_self, k=k)
    # entries never filled (k > candidate count) keep idx -1 / -inf
    valid = jnp.isfinite(vals)
    idxs = jnp.where(valid, idxs, -1)
    return np.asarray(idxs), np.asarray(vals)


def _pow2(size: int) -> int:
    b = 1
    while b < size:
        b <<= 1
    return b


@functools.lru_cache(maxsize=64)
def _id_ramp(row_offset: int, m: int) -> np.ndarray:
    """Cached global-id ramp [row_offset, row_offset + m) — every query
    against a shard's slice needs the same O(n/p) ramp, so it is built
    once per (row_offset, m) instead of per call.  Read-only: callers
    share the cached array."""
    ids = (row_offset + np.arange(m)).astype(np.int32)
    ids.setflags(write=False)
    return ids


def topk_cosine_q(Zn_rows, q, qnodes, *, k: int = 10,
                  block_rows: int = 1 << 14, exclude_self: bool = True,
                  row_offset: int = 0):
    """Top-k of unit-norm query vectors `q` against the unit-norm
    candidate rows `Zn_rows`, which live at global indices
    [row_offset, row_offset + len(Zn_rows)).

    The sharded engine's scatter half: each shard scores the SAME query
    vectors against its owned slice, results carry global ids, and a
    per-query merge over the concatenated per-shard candidates is
    exactly the global answer.  `qnodes` are global query node ids
    for self-exclusion (pass exclude_self=False to keep them).  Score
    ties break by ascending global id (bit-stable across shard counts);
    when k exceeds the candidate count the tail is clamped to
    idx -1 / score -inf.  Returns (indices (q, k) int32,
    scores (q, k) float32) as numpy."""
    m = Zn_rows.shape[0]
    ids = _id_ramp(int(row_offset), int(m))
    return _topk_blocked(Zn_rows, ids, q, qnodes, k=k,
                         block_rows=block_rows,
                         exclude_self=exclude_self)


def _fused_clamp(vals, idxs):
    """Shared unfilled-slot clamp (k > candidate count keeps
    idx -1 / -inf), identical to the blocked scan's post-pass."""
    valid = jnp.isfinite(vals)
    return np.asarray(jnp.where(valid, idxs, -1)), np.asarray(vals)


def topk_cosine_fused(Zn_rows, q, qnodes, *, k: int = 10,
                      block_rows: int = 1 << 14,
                      exclude_self: bool = True, row_offset: int = 0):
    """`topk_cosine_q` as ONE fused pallas scan
    (`kernels.query_fused.topk_fused`): same blocking policy, same
    tie-breaking contract, bit-identical (idx, vals) — but the whole
    blocked merge is a single dispatch with the running top-k resident
    on-chip.  Candidate rows must be unit-norm (a shard's cached Zn);
    use `topk_cosine_fused_norm` on raw rows."""
    m = Zn_rows.shape[0]
    vals, idxs = topk_fused(
        Zn_rows, q, qnodes, k=k, bucket=_bucket_rows(m, block_rows),
        row_offset=int(row_offset), exclude_self=exclude_self,
        normalize=False)
    return _fused_clamp(vals, idxs)


def topk_cosine_fused_norm(Z_rows, q, qnodes, *, k: int = 10,
                           block_rows: int = 1 << 14,
                           exclude_self: bool = True,
                           row_offset: int = 0):
    """Fused normalize+cosine+top-k over RAW candidate rows — the cold
    path of a pallas shard, where Zn has not been materialized yet: the
    kernel normalizes each block in-flight and emits the normalized
    slice alongside the answer, so one pass over Z yields both the
    query result and the shard's Zn cache.  Returns (idx, vals, Zn);
    (idx, vals) are bit-identical to
    ``topk_cosine_q(normalize_rows(Z_rows), ...)``."""
    m = Z_rows.shape[0]
    vals, idxs, Zn = topk_fused(
        Z_rows, q, qnodes, k=k, bucket=_bucket_rows(m, block_rows),
        row_offset=int(row_offset), exclude_self=exclude_self,
        normalize=True)
    idx, v = _fused_clamp(vals, idxs)
    return idx, v, Zn


def topk_cosine_ids(Zn_rows, ids, q, qnodes, *, k: int = 10,
                    block_rows: int = 1 << 14,
                    exclude_self: bool = True):
    """Top-k of unit-norm queries `q` against GATHERED candidate rows
    `Zn_rows` whose global ids are `ids` — the IVF index's per-cell
    scorer, where a cell's member rows are scattered through the owned
    slice.  `ids` must be sorted ascending (cells store sorted member
    lists) so score ties resolve to the ascending global id, exactly as
    the contiguous scan does — that id-order invariant is what makes
    probing all cells bit-identical to the full scan."""
    ids = np.asarray(ids, np.int32)
    return _topk_blocked(Zn_rows, ids, q, qnodes, k=k,
                         block_rows=block_rows,
                         exclude_self=exclude_self)


def merge_topk(idx_parts, val_parts, *, k: int):
    """Merge per-part (idx, val) top-k candidate lists into the global
    top-k (the gather half of the scatter/gather query, and the IVF
    index's cross-cell merge).  Candidates are ordered lexicographically
    by ``(-score, ascending global id)`` via a stable double argsort,
    so the result is bit-stable and INVARIANT in the part order —
    shards and probed cells can arrive however they like.  Unfilled
    slots (idx -1, -inf) lose to any real candidate; a merge with fewer
    than k real candidates keeps the -1 / -inf clamp in its tail."""
    cat_v = jnp.concatenate([jnp.asarray(v) for v in val_parts], 1)
    cat_i = jnp.concatenate([jnp.asarray(i) for i in idx_parts], 1)
    order = jnp.argsort(cat_i, axis=1)            # secondary: id asc
    v = jnp.take_along_axis(cat_v, order, 1)
    i = jnp.take_along_axis(cat_i, order, 1)
    order = jnp.argsort(-v, axis=1)               # primary: score desc
    v = jnp.take_along_axis(v, order, 1)[:, :k]   # (stable: ties keep
    i = jnp.take_along_axis(i, order, 1)[:, :k]   # the id order)
    valid = jnp.isfinite(v)
    return (np.asarray(jnp.where(valid, i, -1)), np.asarray(v))


def topk_cosine(Z, nodes, *, k: int = 10, block_rows: int = 1 << 14,
                exclude_self: bool = True, pre_normalized: bool = False):
    """Top-k cosine neighbors of Z[nodes] over all rows of Z.

    Pass pre_normalized=True when Z rows are already unit-norm (the
    service caches `normalize_rows(Z)` per version so repeated queries
    skip the O(n*K) pass).  Returns (indices (q, k) int32,
    scores (q, k) float32) as numpy."""
    nodes = np.asarray(nodes, np.int32)
    Zn = Z if pre_normalized else normalize_rows(Z)
    q = Zn[jnp.asarray(nodes)]
    return topk_cosine_q(Zn, q, nodes, k=k, block_rows=block_rows,
                         exclude_self=exclude_self)
