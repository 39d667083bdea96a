"""The work an algorithm needs, from the problem's sizes alone: n nodes,
s edges, K classes.  Nothing here depends on how the program lays the
work out (tile size, edge block, blocks per tile, padding), so removing
padding cannot change the denominator."""
from __future__ import annotations


def scatter_work(n: int, s: int, K: int) -> tuple[float, float]:
    """(bytes, flops) of one GEE pass over s edges: each edge's (u, v, w)
    read once (12 B), each node's label and weight read once (8 B), Z
    (n, K) float32 written once; two multiply-adds per edge."""
    return 12.0 * s + 8.0 * n + 4.0 * n * K, 4.0 * s


def roofline_share(bytes_: float, flops: float, seconds: float, pk
                   ) -> float:
    """Percent of the chip's roofline: the least time the work could
    take on the chip over the time it took."""
    if seconds <= 0:
        return None
    least = max(bytes_ / pk.hbm_bw, flops / pk.flops)
    return 100.0 * least / seconds
