"""Partitioning policies for distributed and sharded GEE.

Two axes, matching the two halves of the system:

* **Edge partitioning** (training/offline): the paper gets load
  balance from Ligra's dynamic scheduling; with static SPMD shards we
  get it from randomization: `shuffle_edges` spreads a skewed edge
  list so every shard sees each destination row's contributions in
  about its mean share, and no chip's destination tiles fill far past
  the others' (the distributed backend pads every chip to the fullest
  tile over all chips).

* **Row partitioning** (serving): `RowPartition` splits the n embedding
  rows into contiguous slices, one per `serving.EmbeddingShard`.  GEE's
  map-over-edges form makes this the natural serving split: an edge
  (u, v, w) contributes only to rows u and v, so a delta batch fans out
  only to the shards owning its endpoints (`route_edges`), and each
  shard's routed sub-multiset contains every edge incident to its rows
  — its owned slice of Z is exact in isolation.  A slice is also a
  first-class encoder concept: passing it as
  `EncoderConfig.row_partition=(lo, hi)` makes the backend accumulate
  ONLY the owned (hi - lo, K) rows, which is what gives sharded
  serving its O(n/p) per-shard memory.
"""
from __future__ import annotations

import numpy as np

from repro.graph.edges import Graph


def shuffle_edges(g: Graph, seed: int = 0) -> Graph:
    return g.permuted(np.random.default_rng(seed))


class RowPartition:
    """Contiguous row partition of n nodes across p shards.

    Shard i owns rows [bounds[i], bounds[i+1]) with a fixed stride of
    ceil(n/p) rows per shard (same layout as `ShardedEdgeReader`'s
    contiguous edge split) — the uniform stride is what makes
    `shard_of` an O(1) division, at the cost of the LAST shard holding
    the remainder (up to p-1 rows fewer than the others).  Layouts
    whose remainder would leave a shard with zero rows are rejected.
    The partition is a pure function of (n, p), so every replica — and
    a recovered engine — agrees on ownership without coordination.
    """

    def __init__(self, n: int, p: int):
        if p < 1:
            raise ValueError(f"need p >= 1 shards, got {p}")
        if n < p:
            raise ValueError(f"cannot split {n} rows across {p} shards")
        self.n = int(n)
        self.p = int(p)
        per = (self.n + p - 1) // p
        self.bounds = np.minimum(np.arange(p + 1, dtype=np.int64) * per,
                                 self.n)
        self._per = per
        if self.bounds[-2] >= self.n:
            raise ValueError(
                f"splitting {n} rows across {p} shards (stride {per}) "
                "leaves the last shard empty; use fewer shards")

    def slice(self, shard: int) -> tuple[int, int]:
        """(lo, hi) row range owned by `shard`."""
        return int(self.bounds[shard]), int(self.bounds[shard + 1])

    def slices(self):
        """All (lo, hi) ranges in shard order — each is directly usable
        as an `EncoderConfig.row_partition`."""
        return [self.slice(i) for i in range(self.p)]

    def shard_of(self, nodes) -> np.ndarray:
        """Owning shard id per node (vectorized)."""
        return np.minimum(np.asarray(nodes, np.int64) // self._per,
                          self.p - 1).astype(np.int32)

    def route_nodes(self, nodes: np.ndarray):
        """Split a global node batch by owner.

        Yields (shard, index_into_batch) pairs for shards with work, so
        a scatter/gather caller can reassemble results in request
        order.  Order within a shard's sub-batch follows batch order.
        """
        owner = self.shard_of(nodes)
        for shard in range(self.p):
            idx = np.nonzero(owner == shard)[0]
            if idx.size:
                yield shard, idx

    def route_edges(self, u: np.ndarray, v: np.ndarray, w: np.ndarray):
        """Fan an edge batch out to owning shards.

        Yields (shard, (u, v, w)) sub-batches: shard i receives every
        edge with an endpoint in its rows, ONCE (an intra-shard edge is
        not duplicated).  Edge order is preserved within each
        sub-batch, so routing base ++ deltas equals routing each batch
        and concatenating — the invariant behind the engine's chained
        per-shard fingerprints.  Shards with no incident edges yield an
        empty sub-batch only if `u` itself is empty and p == 1.
        """
        u = np.asarray(u, np.int32)
        v = np.asarray(v, np.int32)
        w = np.asarray(w, np.float32)
        if self.p == 1:
            yield 0, (u, v, w)
            return
        su, sv = self.shard_of(u), self.shard_of(v)
        for shard in range(self.p):
            mask = (su == shard) | (sv == shard)
            if mask.any():
                yield shard, (u[mask], v[mask], w[mask])

    def route_graph(self, g: Graph):
        """`route_edges` over a Graph; yields (shard, sub_graph) with
        `n` preserved (shards embed in global coordinates)."""
        for shard, (u, v, w) in self.route_edges(g.u, g.v, g.w):
            yield shard, Graph(u, v, w, g.n)
