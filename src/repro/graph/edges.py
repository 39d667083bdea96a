"""Graph data structures: weighted directed edge lists.

The paper's convention: a graph G(n, s) is an edge list E in R^{s x 3}
(source, destination, weight); undirected graphs are two symmetric
directed edges; unweighted graphs have unit weights.  Labels
Y in {0..K}^n with 0 = unknown (paper) are remapped here to
{-1 = unknown, 0..K-1} for 0-based indexing.
"""
from __future__ import annotations

import hashlib
from dataclasses import dataclass
from typing import Optional

import numpy as np

try:                                   # fast path where available
    import xxhash

    def _new_hash():
        return xxhash.xxh3_128()
except ImportError:                    # stdlib fallback, same interface
    def _new_hash():
        return hashlib.blake2b(digest_size=16)


def _hash_edges(h, u, v, w) -> None:
    """Feed (u, v, w) into hasher `h` in canonical dtypes, so two graphs
    with equal content but different array dtypes/layout agree."""
    for arr, dt in ((u, np.int32), (v, np.int32), (w, np.float32)):
        h.update(np.ascontiguousarray(arr, dt).data)


class FingerprintAccumulator:
    """Streaming edge-fingerprint builder: feed (u, v, w) batches in
    order, read `digest()` at the end.

    One hasher per column, combined at digest time — so the value
    depends only on the CONTENT streamed, never on how it was chunked
    (a reader with chunk_size=128 and one with 512 agree, and both
    agree with a whole-array `edge_fingerprint`)."""

    def __init__(self, n: int):
        self._n = int(n)
        self._cols = (_new_hash(), _new_hash(), _new_hash())

    def update(self, u, v, w) -> "FingerprintAccumulator":
        for h, arr, dt in zip(self._cols,
                              (u, v, w),
                              (np.int32, np.int32, np.float32)):
            h.update(np.ascontiguousarray(arr, dt).data)
        return self

    def digest(self) -> str:
        h = _new_hash()
        h.update(np.int64(self._n).tobytes())
        for col in self._cols:
            h.update(col.digest())
        return h.hexdigest()


def edge_fingerprint(n: int, u, v, w) -> str:
    """Content fingerprint of an edge multiset: hash over (n, u, v, w).

    O(s) over raw bytes — cheap relative to any plan build (sorting,
    Laplacian degrees), and the cross-process cache key for the
    encoder's persistent plan tier.  ORDER-SENSITIVE by design: plan
    artifacts (packing layouts, chunk boundaries) depend on edge order,
    so a permuted multiset correctly reads as different content."""
    return FingerprintAccumulator(n).update(u, v, w).digest()


def extend_fingerprint(fp: str, u, v, w) -> str:
    """Chain an appended edge batch onto an existing fingerprint.

    fp' = H(fp || u || v || w): lets an append-only log (the serving
    store) maintain its multiset fingerprint in O(batch) per delta
    instead of rehashing the full edge list.  The chained value differs
    from `edge_fingerprint` of the concatenated arrays — that is fine:
    any process replaying the same base + delta sequence reaches the
    same value, which is all a cache key needs."""
    h = _new_hash()
    h.update(bytes.fromhex(fp))
    _hash_edges(h, u, v, w)
    return h.hexdigest()


@dataclass
class Graph:
    """Edge-list graph. u, v: int32 (s,); w: float32 (s,); n nodes."""
    u: np.ndarray
    v: np.ndarray
    w: np.ndarray
    n: int

    @property
    def s(self) -> int:
        return int(self.u.shape[0])

    def fingerprint(self) -> str:
        """Content fingerprint (see `edge_fingerprint`), computed once
        and cached on the instance.  Sources that already know the
        fingerprint (the serving store's incrementally-maintained one,
        a generator's parameter hash) pre-stamp `_fp` so materializing
        a graph never forces a rehash.  Assumes the arrays are not
        mutated in place afterwards (nothing in this codebase does)."""
        fp = getattr(self, "_fp", None)
        if fp is None:
            fp = edge_fingerprint(self.n, self.u, self.v, self.w)
            self._fp = fp
        return fp

    def validate(self) -> None:
        assert self.u.shape == self.v.shape == self.w.shape
        if self.s == 0:        # empty edge list (e.g. an empty delta batch)
            return
        assert self.u.min() >= 0 and self.u.max() < self.n
        assert self.v.min() >= 0 and self.v.max() < self.n

    def symmetrize(self) -> "Graph":
        """Undirected -> two symmetric directed edges."""
        return Graph(np.concatenate([self.u, self.v]),
                     np.concatenate([self.v, self.u]),
                     np.concatenate([self.w, self.w]), self.n)

    def degrees(self) -> np.ndarray:
        """Weighted out+in degree (the Laplacian normalizer)."""
        d = np.zeros(self.n, np.float64)
        np.add.at(d, self.u, self.w)
        np.add.at(d, self.v, self.w)
        return d.astype(np.float32)

    def permuted(self, rng: np.random.Generator) -> "Graph":
        """Random edge order (load-balance for static sharding)."""
        p = rng.permutation(self.s)
        return Graph(self.u[p], self.v[p], self.w[p], self.n)

    def pad_to(self, s_pad: int) -> "Graph":
        """Pad with zero-weight self-loops of node 0 (no-op edges).

        Contract (regression-tested): padding preserves `n` and is
        invisible to every downstream consumer — `degrees()` and the
        Laplacian `deg` precompute are unchanged (the pad edges carry
        w = 0 exactly, so they add nothing to either endpoint), and Z
        is unchanged for any labeling (a zero-weight contribution is a
        no-op regardless of node 0's label)."""
        extra = s_pad - self.s
        assert extra >= 0
        if extra == 0:
            return self
        assert self.n >= 1, "cannot pad a graph with no nodes"
        z = np.zeros(extra, np.int32)
        return Graph(np.concatenate([np.asarray(self.u, np.int32), z]),
                     np.concatenate([np.asarray(self.v, np.int32), z]),
                     np.concatenate([np.asarray(self.w, np.float32),
                                     np.zeros(extra, np.float32)]),
                     self.n)


def bucket_size(size: int, floor: int = 256) -> int:
    """Next power-of-two >= size (>= floor) — the shared batch-padding
    policy that keeps jitted kernels at one compile per bucket, not per
    batch size (used by the encoder's delta path and the serving store)."""
    b = floor
    while b < size:
        b <<= 1
    return b


def chunk_edges(u: np.ndarray, v: np.ndarray, w: np.ndarray,
                chunk_size: int, floor: int = 256):
    """Yield (u, v, w) host chunks of at most `chunk_size` edges; the
    tail chunk is padded to a power-of-two bucket with zero-weight
    node-0 self-loops (no-op edges) so chunked consumers reuse jit
    compilations across changing edge counts.  Non-tail chunks are
    views (no copy).  THE one chunk-and-pad policy — used by the
    encoder's streaming backend and the serving store alike."""
    s = int(u.shape[0])
    for off in range(0, s, chunk_size):
        end = min(off + chunk_size, s)
        m = end - off
        if m < chunk_size:
            pad = bucket_size(m, floor) - m
            yield (np.concatenate([u[off:end], np.zeros(pad, np.int32)]),
                   np.concatenate([v[off:end], np.zeros(pad, np.int32)]),
                   np.concatenate([w[off:end], np.zeros(pad, np.float32)]))
        else:
            yield u[off:end], v[off:end], w[off:end]


def make_labels(n: int, K: int, labeled_frac: float,
                rng: np.random.Generator,
                true_labels: Optional[np.ndarray] = None) -> np.ndarray:
    """Paper setup: labels uniform over [0, K) for `labeled_frac` of nodes
    chosen uniformly at random; -1 elsewhere.  If true_labels given,
    reveal those instead of random ones (SBM quality experiments)."""
    Y = np.full(n, -1, np.int32)
    m = max(1, int(n * labeled_frac))
    idx = rng.choice(n, size=m, replace=False)
    if true_labels is not None:
        Y[idx] = true_labels[idx]
    else:
        Y[idx] = rng.integers(0, K, size=m)
    return Y
