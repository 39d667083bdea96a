"""EncoderConfig: everything about *what* to compute, none of *how*.

The paper's algorithm has one mathematical definition (Z = scatter-add
of per-edge label contributions) and many execution strategies.  The
config captures the math-level choices — number of classes, Laplacian
scaling, refinement schedule, output dtype — plus the per-backend
tuning knobs (tile sizes, chunk sizes) that change
performance but never the answer.  Frozen and hashable so plans can be
keyed on it.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple, Union


@dataclass(frozen=True)
class EncoderConfig:
    """Configuration for :class:`repro.encoder.Embedder`.

    Math-level options (change Z):
      K           number of classes / embedding dimension.
      laplacian   GEE paper's Laplacian scaling w' = w/sqrt(deg_u*deg_v).
                  Applied once at plan time (degrees are label-free), so
                  every backend sees pre-scaled weights.
      dtype       output dtype of ``transform`` ("float32"/"bfloat16"/...).
                  Z is always accumulated in float32.

    Refinement (unsupervised GEE clustering, ``Embedder.refine``):
      refine_iters   embed -> k-means -> reassign rounds.
      kmeans_iters   k-means steps per round.

    Row partitioning (owned-rows accumulate — shrinks Z, not its rows):
      row_partition  (lo, hi) global row range this Embedder OWNS (a
                  `graph.partition.RowPartition` slice), or None for
                  the full embedding.  When set, the plan buckets edge
                  contributions by owned destination (remapped to local
                  rows [0, hi - lo)), the backend allocates only an
                  (hi - lo, K) accumulator, and the fitted `Z_` holds
                  exactly the owned rows — labels stay GLOBAL (an owned
                  row's value depends on its neighbors' labels), and
                  node-id arguments to `transform`/`predict` stay
                  global too.  The partition joins the plan-cache key
                  (tier 1 and tier 2), so a resharded deployment can
                  never hit a stale plan.  Supported by the numpy /
                  xla / streaming / pallas backends (the distributed
                  backend shards internally instead).

    Backend tuning (never change Z, only speed/memory):
      backend     execution strategy by registry name, or "auto"
                  (default) — resolved at plan time from (n, s, device
                  kind, device count) via the overridable policy table
                  in `repro.encoder.backends.AUTO_POLICY`.  An explicit
                  `Embedder(..., backend=...)` argument overrides this.
      tile_n, edge_block, interpret   Pallas kernel geometry.
      chunk_size                      streaming chunk length.
    """

    K: int
    laplacian: bool = False
    dtype: str = "float32"
    backend: str = "auto"
    row_partition: Optional[Tuple[int, int]] = None
    # refinement
    refine_iters: int = 10
    kmeans_iters: int = 3
    # pallas: interpret is "auto" (compiled on TPU/GPU, interpreter
    # elsewhere — resolved at plan time by kernels.resolve_interpret),
    # or an explicit bool to force a mode
    tile_n: int = 256
    edge_block: int = 512
    interpret: Union[bool, str] = "auto"
    # streaming
    chunk_size: int = 1 << 20

    def __post_init__(self) -> None:
        if self.K < 1:
            raise ValueError(f"K must be >= 1, got {self.K}")
        if self.chunk_size < 1:
            raise ValueError("chunk_size must be >= 1")
        if not isinstance(self.interpret, bool) and \
                self.interpret != "auto":
            raise ValueError(
                f"interpret must be True, False, or 'auto', got "
                f"{self.interpret!r}")
        if self.row_partition is not None:
            try:
                lo, hi = self.row_partition
            except (TypeError, ValueError):
                raise ValueError(
                    f"row_partition must be a (lo, hi) pair, got "
                    f"{self.row_partition!r}") from None
            if not (0 <= int(lo) < int(hi)):
                raise ValueError(
                    f"row_partition needs 0 <= lo < hi, got ({lo}, {hi})")
            # normalize (lists, np ints) so the config stays hashable
            # and its cache token is canonical
            object.__setattr__(self, "row_partition",
                               (int(lo), int(hi)))
