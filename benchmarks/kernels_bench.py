"""Kernel-path microbenchmarks (CPU interpret mode timings are NOT TPU
performance — emitted for regression tracking of the wrappers, plus the
jnp GEE hot path which IS the CPU production path).  GEE paths go
through the unified Embedder so what we time is what callers run.

Pallas rows report the RESOLVED compile/interpret mode
(`kernels.resolve_interpret`) in their derived column, and the suite
prints a loud warning when a "pallas" row was measured in interpret
mode — an interpreted kernel timing mistaken for kernel performance is
exactly the bug the auto-resolved mode exists to surface.  The
``*_roofline`` rows report achieved HBM bandwidth from the
`repro.launch.autotune` traffic models, and a roofline share only for
compiled kernels timed on a TPU (``not_measured`` otherwise)."""
from __future__ import annotations

import jax

from benchmarks import common
from benchmarks.common import emit, time_it
from repro.encoder import Embedder, EncoderConfig
from repro.graph.edges import make_labels
from repro.graph.generators import erdos_renyi
from repro.kernels import ops
from repro.kernels.gee_scatter import interpret_mode_name, resolve_interpret
from repro.launch.autotune import (roofline_share, scatter_traffic_bytes,
                                   topk_traffic_bytes)

import numpy as np


def _topk_m() -> int:
    return common.pick(50_000, 2_000)


def expected_keys() -> list:
    """Schema for `benchmarks.run`'s silently-empty-driver check."""
    sizes = common.pick((1_000_000, 4_000_000), (4_000, 8_000))
    return ([f"kernels/gee_xla_scatter/s{s}" for s in sizes]
            + ["kernels/gee_pallas/s16000",
               "kernels/gee_pallas_owned/s16000",
               "kernels/gee_scatter_roofline/s16000",
               f"kernels/topk_fused/m{_topk_m()}",
               f"kernels/topk_fused_roofline/m{_topk_m()}",
               "kernels/flash_attn_interpret/s256"])


def _bw_note(moved: int, seconds: float, mode: str) -> str:
    gbps = moved / seconds / 1e9 if seconds > 0 else 0.0
    frac = roofline_share(gbps * 1e9, mode)
    share = "not_measured" if frac is None else f"{frac * 100:.3f}%"
    return f"achieved={gbps:.3f}GB/s roofline_share={share} mode={mode}"


def run() -> None:
    rng = np.random.default_rng(0)
    n, k = common.pick((100_000, 50), (1_000, 8))
    # jnp scatter hot path at a few scales
    for s in common.pick((1_000_000, 4_000_000), (4_000, 8_000)):
        g = erdos_renyi(n, s, seed=s)
        Y = make_labels(g.n, k, 0.1, rng)
        emb = Embedder(EncoderConfig(K=k), backend="xla").fit(g, Y)
        t = time_it(lambda emb=emb, Y=Y: emb.refit(Y).Z_,
                    warmup=1, iters=3)
        emit(f"kernels/gee_xla_scatter/s{s}", t,
             f"edges_per_s={s / t:,.0f}")

    # pallas gee kernel, mode resolved per platform; the plan
    # (destination packing) is cached, so refits time the kernel alone
    interp = resolve_interpret("auto")
    mode = interpret_mode_name(interp)
    g = erdos_renyi(2_000, 16_000, seed=7)
    Y = make_labels(g.n, 16, 0.2, rng)
    emb = Embedder(EncoderConfig(K=16, tile_n=256, edge_block=256),
                   backend="pallas").fit(g, Y)
    t = time_it(lambda: emb.refit(Y).Z_, warmup=1, iters=2)
    emit("kernels/gee_pallas/s16000", t, f"mode={mode}")
    d = emb._plan.data
    moved = scatter_traffic_bytes(d["T"], d["rows"].shape[1],
                                  d["rows"].shape[-1], 256, d["kdim"])
    emit("kernels/gee_scatter_roofline/s16000", t,
         _bw_note(moved, t, mode))

    # owned-rows pallas: same graph, a proper sub-range partition —
    # the kernel plus the O(n/p) accumulator path sharded rebuilds use
    emb_o = Embedder(EncoderConfig(K=16, tile_n=256, edge_block=256,
                                   row_partition=(0, 1_000)),
                     backend="pallas").fit(g, Y)
    t = time_it(lambda: emb_o.refit(Y).Z_, warmup=1, iters=2)
    emit("kernels/gee_pallas_owned/s16000", t,
         f"n_local=1000 mode={mode}")

    # fused normalize+cosine+top-k query kernel over a candidate slice
    from repro.serving import queries as Q
    m, K, nq, topk = _topk_m(), 16, 32, 10
    Z = np.asarray(rng.normal(size=(m, K)), np.float32)
    import jax.numpy as jnp
    Zn = Q.normalize_rows(jnp.asarray(Z))
    qnodes = rng.integers(0, m, nq).astype(np.int32)
    q = Zn[jnp.asarray(qnodes)]
    block_rows = 1 << 14
    t = time_it(lambda: Q.topk_cosine_fused(Zn, q, qnodes, k=topk,
                                            block_rows=block_rows),
                warmup=1, iters=2)
    emit(f"kernels/topk_fused/m{m}", t, f"nq={nq} k={topk} mode={mode}")
    bucket = Q._bucket_rows(m, block_rows)
    moved = topk_traffic_bytes(m, K, nq, topk, bucket)
    emit(f"kernels/topk_fused_roofline/m{m}", t, _bw_note(moved, t, mode))

    if interp:
        print("WARNING: pallas rows above were measured in INTERPRET "
              "mode (no pallas lowering on "
              f"{jax.default_backend()!r}) — these are wrapper "
              "correctness timings, NOT kernel performance; rerun on "
              "TPU/GPU for compiled numbers.")

    # flash attention kernel interpret vs jnp reference
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 4, 256, 64))
    k = jax.random.normal(ks[1], (1, 2, 256, 64))
    v = jax.random.normal(ks[2], (1, 2, 256, 64))
    t = time_it(lambda: ops.flash_attention(q, k, v, bq=128, bk=128),
                warmup=1, iters=2)
    emit("kernels/flash_attn_interpret/s256", t, "correctness path")


if __name__ == "__main__":
    run()
