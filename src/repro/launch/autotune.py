"""Kernel-geometry autotuning against the roofline bandwidth model.

The GEE scatter and fused top-k kernels are memory-bound by design
(the paper's whole point: edge-parallel scatter at memory bandwidth),
so the right figure of merit for a geometry candidate is **achieved
HBM fraction**: bytes the kernel must move (from the traffic models
below) divided by measured wall time, over the chip's peak HBM
bandwidth (`roofline.peaks` of the device kind).

Search: greedy coordinate descent over the per-kernel geometry space —
sweep one knob at a time holding the others at the incumbent, repeat
until a full round improves nothing.  The spaces are tiny (a few
points per knob) so this converges in two or three rounds; it exists
so a new chip/topology retunes `TILE_N`/`EDGE_BLOCK`/`block_rows` with
one command instead of a hand sweep:

    PYTHONPATH=src python -m repro.launch.hillclimb gee-scatter-tune
    PYTHONPATH=src python -m repro.launch.hillclimb gee-topk-tune

On CPU the kernels run in interpret mode, so absolute times are
interpreter throughput, NOT kernel performance: the tuner prints the
resolved mode and reports no roofline share for such timings
(`benchmarks.kernels_bench` does the same).  On TPU the same commands
tune the compiled kernels and report their share of the chip's peak.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, Optional, Tuple

import jax
import numpy as np

from repro.launch.roofline import peaks

#: geometry spaces swept by the coordinate descent (ascending so the
#: sweep output reads as a size scan)
SCATTER_SPACE: Dict[str, Tuple[int, ...]] = {
    "tile_n": (64, 128, 256, 512),
    "edge_block": (128, 256, 512, 1024),
}
TOPK_SPACE: Dict[str, Tuple[int, ...]] = {
    "block_rows": (256, 1024, 4096, 16384),
}


def median_time(fn: Callable[[], object], *, warmup: int = 1,
                iters: int = 3) -> float:
    """Median wall seconds per call, async-dispatch aware."""
    for _ in range(warmup):
        jax.block_until_ready(fn())
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def scatter_traffic_bytes(T: int, bpt: int, eb: int, tile_n: int,
                          kdim: int) -> int:
    """HBM bytes one scatter pass must move: the three packed edge
    arrays (int32 rows/cls + f32 val) stream through once, and each Z
    tile — VMEM-resident across its inner grid dimension — is written
    once.  A lower bound (ignores the on-device class/value resolve
    reads), which is what an achieved-fraction denominator wants."""
    return 3 * T * bpt * eb * 4 + T * tile_n * kdim * 4


def topk_traffic_bytes(m: int, K: int, nq: int, k: int,
                       bucket: int) -> int:
    """HBM bytes one fused top-k scan must move: the candidate slice
    streams once, the query block re-reads per candidate block (it is
    small but revisited), and the (vals, idxs) running block writes
    once."""
    nb = max(1, -(-max(m, 1) // bucket))
    return m * K * 4 + nb * nq * K * 4 + nq * k * 8


def _coordinate_descent(space: Dict[str, Tuple[int, ...]],
                        measure: Callable[[dict], float],
                        start: dict, *, log: Callable = print) -> dict:
    """Greedy per-knob sweep to a local optimum of `measure` (seconds,
    lower is better).  Returns {'best': cfg, 'seconds': t, 'trace':
    [(cfg, t), ...]} with every point measured."""
    best = dict(start)
    trace = []
    best_t = measure(best)
    trace.append((dict(best), best_t))
    improved = True
    while improved:
        improved = False
        for knob, points in space.items():
            for p in points:
                if p == best[knob]:
                    continue
                cand = {**best, knob: p}
                t = measure(cand)
                trace.append((dict(cand), t))
                if t < best_t:
                    best, best_t = cand, t
                    improved = True
            log(f"  {knob}: best so far {best} -> {best_t * 1e3:.2f} ms")
    return {"best": best, "seconds": best_t, "trace": trace}


def tune_scatter(n: int = 20_000, s: int = 200_000, K: int = 16, *,
                 space: Dict[str, Tuple[int, ...]] = None,
                 iters: int = 2, log: Callable = print) -> dict:
    """Tune (tile_n, edge_block) for the GEE scatter kernel on an
    Erdos-Renyi workload of (n, s); refits time the kernel alone (the
    plan's destination packing is cached per geometry)."""
    from repro.encoder import Embedder, EncoderConfig
    from repro.graph.edges import make_labels
    from repro.graph.generators import erdos_renyi
    from repro.kernels.gee_scatter import (interpret_mode_name,
                                           resolve_interpret)
    space = dict(SCATTER_SPACE if space is None else space)
    g = erdos_renyi(n, s, seed=0)
    Y = make_labels(g.n, K, 0.2, np.random.default_rng(0))
    mode = interpret_mode_name(resolve_interpret("auto"))
    log(f"scatter tune: n={n} s={s} K={K} mode={mode}")

    embs: dict = {}

    def measure(cfg: dict) -> float:
        key = (cfg["tile_n"], cfg["edge_block"])
        if key not in embs:
            embs[key] = Embedder(
                EncoderConfig(K=K, tile_n=cfg["tile_n"],
                              edge_block=cfg["edge_block"]),
                backend="pallas", plan_cache=None).fit(g, Y)
        e = embs[key]
        return median_time(lambda: e.refit(Y).Z_, iters=iters)

    out = _coordinate_descent(space, measure, {
        "tile_n": space["tile_n"][0], "edge_block": space["edge_block"][0],
    }, log=log)
    best = out["best"]
    e = embs[(best["tile_n"], best["edge_block"])]
    d = e._plan.data
    moved = scatter_traffic_bytes(d["T"], d["rows"].shape[1],
                                  d["rows"].shape[-1], best["tile_n"],
                                  d["kdim"])
    out.update(_bandwidth(moved, out["seconds"], mode, log=log))
    return out


def tune_topk(m: int = 50_000, K: int = 16, nq: int = 64,
              k: int = 10, *,
              space: Dict[str, Tuple[int, ...]] = None,
              iters: int = 2, log: Callable = print) -> dict:
    """Tune block_rows for the fused normalize+cosine+top-k kernel over
    an (m, K) candidate slice."""
    import jax.numpy as jnp
    from repro.kernels.gee_scatter import (interpret_mode_name,
                                           resolve_interpret)
    from repro.serving import queries as Q
    space = dict(TOPK_SPACE if space is None else space)
    rng = np.random.default_rng(0)
    Z = jnp.asarray(rng.normal(size=(m, K)).astype(np.float32))
    Zn = Q.normalize_rows(Z)
    qnodes = rng.integers(0, m, nq).astype(np.int32)
    q = Zn[jnp.asarray(qnodes)]
    mode = interpret_mode_name(resolve_interpret("auto"))
    log(f"topk tune: m={m} K={K} nq={nq} k={k} mode={mode}")

    def measure(cfg: dict) -> float:
        return median_time(
            lambda: Q.topk_cosine_fused(Zn, q, qnodes, k=k,
                                        block_rows=cfg["block_rows"]),
            iters=iters)

    out = _coordinate_descent(space, measure,
                              {"block_rows": space["block_rows"][0]},
                              log=log)
    bucket = Q._bucket_rows(m, out["best"]["block_rows"])
    moved = topk_traffic_bytes(m, K, nq, k, bucket)
    out.update(_bandwidth(moved, out["seconds"], mode, log=log))
    return out


def roofline_share(bytes_per_s: float, mode: str) -> Optional[float]:
    """Achieved over peak HBM bandwidth of this device, or None where
    the timing is not a chip's (CPU, or a kernel in interpret mode)."""
    dev = jax.devices()[0]
    if mode == "interpret" or dev.platform != "tpu":
        return None
    return bytes_per_s / peaks(dev.device_kind).hbm_bw


def _bandwidth(moved_bytes: int, seconds: float, mode: str, *,
               log: Callable = print) -> dict:
    gbps = moved_bytes / seconds / 1e9 if seconds > 0 else 0.0
    frac = roofline_share(gbps * 1e9, mode)
    share = ("not measured (no chip timing)" if frac is None
             else f"{frac * 100:.2f}% of peak HBM")
    log(f"  traffic {moved_bytes / 1e6:.1f} MB, achieved "
        f"{gbps:.2f} GB/s, roofline share {share} [{mode} mode]")
    return {"moved_bytes": moved_bytes, "achieved_gbps": gbps,
            "roofline_frac": frac, "mode": mode}
