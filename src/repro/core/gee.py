"""One-Hot Graph Encoder Embedding (GEE) — the paper's algorithm in JAX.

Label convention: Y in {-1 = unknown, 0..K-1}.

The serial edge loop with atomic ``writeAdd`` becomes a vectorized
scatter-add (XLA ``scatter`` with add-combiner): race-free by
construction and bitwise deterministic, computing exactly the same Z.

Variants:
  * ``gee``            — jit-able single-device embedding (weighted,
                          directed; symmetric contribution per the paper)
  * ``laplacian=True`` — the GEE paper's Laplacian scaling
                          (w' = w / sqrt(deg_u * deg_v))
  * ``gee_refine``     — unsupervised GEE clustering: embed -> k-means
                          reassign -> re-embed (Shen et al.'s iterative
                          refinement; replaces the Leiden bootstrap)
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp


def class_weights(Y: jnp.ndarray, K: int) -> jnp.ndarray:
    """Per-class projection weight (K,): 1/n_k for the n_k nodes labeled
    k, 0 for a class no node carries.  The pallas backend applies it per
    column of Z; `make_w` spreads it over the nodes."""
    labeled = Y >= 0
    counts = jnp.zeros(K, jnp.float32).at[jnp.where(labeled, Y, 0)].add(
        labeled.astype(jnp.float32))
    return jnp.where(counts > 0, 1.0 / jnp.maximum(counts, 1.0), 0.0)


def make_w(Y: jnp.ndarray, K: int,
           class_w: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Per-node projection weight: 1/|class(Y_i)| (0 for unlabeled),
    which the scatter backends apply per contribution.  `class_w`: the
    `class_weights(Y, K)` the caller already holds."""
    if class_w is None:
        class_w = class_weights(Y, K)
    return jnp.where(Y >= 0, class_w[jnp.maximum(Y, 0)], 0.0)


def edge_contributions(u, v, w, Y, Wv):
    """Per-directed-edge (dst, class, value) pairs — both directions.

    Returns (dst (2s,), cls (2s,), val (2s,)).  Edges whose source label
    is unknown contribute value 0 (class index clamped to 0)."""
    yv, yu = Y[v], Y[u]
    dst = jnp.concatenate([u, v])
    cls = jnp.concatenate([jnp.maximum(yv, 0), jnp.maximum(yu, 0)])
    val = jnp.concatenate([
        jnp.where(yv >= 0, Wv[v] * w, 0.0),
        jnp.where(yu >= 0, Wv[u] * w, 0.0)])
    return dst, cls, val


@functools.partial(jax.jit, static_argnames=("K", "n", "laplacian"))
def gee(u, v, w, Y, *, K: int, n: int, laplacian: bool = False,
        deg: Optional[jnp.ndarray] = None,
        Wv: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """One-pass GEE embedding. Returns Z (n, K) float32.

    Wv: optional precomputed projection weights (callers that own the
    weights — `repro.encoder.Embedder` — pass them; default derives
    them from Y, like the optional `deg` precompute)."""
    w = w.astype(jnp.float32)
    if laplacian:
        if deg is None:
            deg = (jnp.zeros(n, jnp.float32).at[u].add(w).at[v].add(w))
        scale = jax.lax.rsqrt(jnp.maximum(deg, 1.0))
        w = w * scale[u] * scale[v]
    if Wv is None:
        Wv = make_w(Y, K)
    dst, cls, val = edge_contributions(u, v, w, Y, Wv)
    return jnp.zeros((n, K), jnp.float32).at[dst, cls].add(val)


def gee_dense_oracle(u, v, w, Y, K: int, n: int) -> jnp.ndarray:
    """O(n^2) dense formulation Z = A @ Wmat — tiny-graph test oracle.

    Wmat is the paper's actual (n, K) one-hot projection matrix; the
    adjacency is symmetrized the way Algorithm 1's two updates imply."""
    A = jnp.zeros((n, n), jnp.float32).at[u, v].add(w).at[v, u].add(w)
    Wv = make_w(Y, K)
    onehot = jax.nn.one_hot(jnp.maximum(Y, 0), K) * (Y >= 0)[:, None]
    Wmat = onehot * Wv[:, None]
    return A @ Wmat


# ---------------------------------------------------------------------------
# Streaming / incremental updates (beyond-paper: dynamic graphs)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("K",))
def gee_apply_delta(Z, u, v, w, Y, Wv, *, K: int, sign: float = 1.0):
    """Incremental GEE: fold an edge batch into an existing Z.

    Exact by additivity (Z is linear in the edge multiset — property-
    tested), so edge insertions (sign=+1) and deletions (sign=-1) cost
    O(batch) instead of a full O(s) re-embed.  Label changes still
    require re-embedding the affected class columns (W changes).
    Wv must be the same projection weights Z was built with."""
    dst, cls, val = edge_contributions(u, v, w.astype(jnp.float32), Y, Wv)
    return Z.at[dst, cls].add(sign * val)


def gee_streaming(chunks, Y, *, K: int, n: int,
                  Wv: Optional[jnp.ndarray] = None):
    """Single-pass streaming embed over an iterator of (u, v, w) chunks —
    the out-of-core ingestion path (pairs with graph.io.ShardedEdgeReader).
    Wv: optional owned projection weights, as in `gee`."""
    if Wv is None:
        Wv = make_w(Y, K)
    Z = jnp.zeros((n, K), jnp.float32)
    for (u, v, w) in chunks:
        Z = gee_apply_delta(Z, u, v, w, Y, Wv, K=K)
    return Z


# ---------------------------------------------------------------------------
# Owned-rows (partitioned) accumulate: O(n/p) accumulators per shard
# ---------------------------------------------------------------------------
#
# A row partition assigns each worker the contiguous Z rows [lo, hi).
# Because GEE maps over edges and an edge (u, v, w) touches only rows u
# and v, the contributions landing in a worker's rows are a filterable
# subset of the edge multiset: (dst, src, w) triples with dst in
# [lo, hi), remapped to local row dst - lo.  These kernels scatter that
# pre-bucketed form into an (n_local, K) accumulator — the labels Y and
# projection weights Wv stay GLOBAL (an owned row's value depends on
# its neighbors' labels, which may live on other workers), only the
# accumulator shrinks.


def owned_edge_contributions(src, w, Y, Wv):
    """Per-contribution (class, value) for owned-destination triples.

    `src` is the GLOBAL label-donor node of each contribution; unknown
    source labels contribute value 0 (class clamped to 0), exactly as
    in `edge_contributions` — this is one direction of that function,
    already filtered/remapped by the host plan."""
    ys = Y[src]
    cls = jnp.maximum(ys, 0)
    val = jnp.where(ys >= 0, Wv[src] * w, 0.0)
    return cls, val


@functools.partial(jax.jit, static_argnames=("K", "n_local"))
def gee_owned(rows, src, w, Y, Wv, *, K: int, n_local: int):
    """One-pass GEE over owned-destination contributions.

    rows: LOCAL destination rows in [0, n_local); src: GLOBAL label
    donors; Y/Wv: global labels and projection weights.  Returns the
    (n_local, K) owned slice of Z — bit-identical in content to the
    corresponding rows of the full accumulate."""
    cls, val = owned_edge_contributions(src, w.astype(jnp.float32), Y, Wv)
    return jnp.zeros((n_local, K), jnp.float32).at[rows, cls].add(val)


@functools.partial(jax.jit, static_argnames=("K",))
def gee_apply_delta_owned(Z, rows, src, w, Y, Wv, *, K: int,
                          sign: float = 1.0):
    """Fold owned-destination contributions into an (n_local, K) slice
    (the partitioned twin of `gee_apply_delta`; exact by linearity).
    Padded slots carry w = 0 and are no-ops for any labeling."""
    cls, val = owned_edge_contributions(src, w.astype(jnp.float32), Y, Wv)
    return Z.at[rows, cls].add(sign * val)


def gee_streaming_owned(chunks, Y, *, K: int, n_local: int,
                        Wv: Optional[jnp.ndarray] = None):
    """Chunked owned-rows accumulate: device working set is O(chunk)
    contribution data plus the (n_local, K) slice — the shard-rebuild
    path.  `chunks` yields (rows, src, w) triples."""
    if Wv is None:
        Wv = make_w(Y, K)
    Z = jnp.zeros((n_local, K), jnp.float32)
    for (rows, src, w) in chunks:
        Z = gee_apply_delta_owned(Z, rows, src, w, Y, Wv, K=K)
    return Z


# ---------------------------------------------------------------------------
# Unsupervised refinement (GEE clustering)
# ---------------------------------------------------------------------------


def _kmeans_assign(Z, centers):
    d2 = (jnp.sum(Z * Z, 1, keepdims=True)
          - 2 * Z @ centers.T + jnp.sum(centers * centers, 1))
    return jnp.argmin(d2, axis=1).astype(jnp.int32)


def _kmeans_update(Z, labels, K):
    onehot = jax.nn.one_hot(labels, K, dtype=Z.dtype)
    sums = onehot.T @ Z
    counts = onehot.sum(0)[:, None]
    return sums / jnp.maximum(counts, 1.0)


def kmeans_refine_round(Z, labels, Y0, K: int, kmeans_iters: int):
    """One refinement round's label update: row-normalize Z, k-means,
    reassign with the supervised labels in Y0 pinned.  THE one copy of
    the refinement math — shared by `gee_refine` and
    `repro.encoder.Embedder.refine`."""
    Zn = Z / jnp.maximum(jnp.linalg.norm(Z, axis=1, keepdims=True), 1e-9)
    centers = _kmeans_update(Zn, labels, K)
    for _ in range(kmeans_iters):
        assign = _kmeans_assign(Zn, centers)
        centers = _kmeans_update(Zn, assign, K)
    return jnp.where(Y0 >= 0, Y0, assign)


@functools.partial(jax.jit, static_argnames=("K", "n", "iters", "kmeans_iters"))
def gee_refine(u, v, w, Y0, key, *, K: int, n: int, iters: int = 10,
               kmeans_iters: int = 3):
    """Iterative GEE clustering: embed with current labels, k-means in the
    K-dim embedding, reassign, repeat.  Y0 may be all-unknown (-1), in
    which case labels bootstrap from a random assignment."""
    rand = jax.random.randint(key, (n,), 0, K, jnp.int32)
    labels = jnp.where(Y0 >= 0, Y0, rand)

    def body(labels, _):
        Z = gee(u, v, w, labels, K=K, n=n)
        labels = kmeans_refine_round(Z, labels, Y0, K, kmeans_iters)
        return labels, None

    labels, _ = jax.lax.scan(body, labels, None, length=iters)
    Z = gee(u, v, w, labels, K=K, n=n)
    return Z, labels
