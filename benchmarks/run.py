# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness entry point.

    PYTHONPATH=src python -m benchmarks.run [--only table1,fig4,...]

Suites:
    table1   — paper Table I analog (python/numpy/XLA GEE runtimes)
    fig3     — strong scaling (subprocess device sweep)
    fig4     — Erdős–Rényi edge-count linearity
    kernels  — kernel-path microbenches
    encoder  — unified Embedder API: per-backend edges/s side by side
               + plan-cache (host packing removed on refit)
    serving  — online-service update latency vs full re-embed + queries
               + sharded-engine rows incl. per-shard accumulator memory
    index    — IVF index QPS + recall@10 vs the exact full scan
    roofline — per-cell roofline terms from dry-run artifacts

Schema check: after each suite runs, the rows it emitted are checked
against the driver's ``expected_keys()`` declaration — a driver that
silently emits nothing (or loses a row to a refactor) FAILS the run
instead of passing vacuously (the `make bench-smoke` CI gate relies on
this).
"""
from __future__ import annotations

import argparse
import importlib
import sys
import traceback

SUITES = {
    "table1": "benchmarks.table1_runtimes",
    "fig4": "benchmarks.fig4_edges",
    "kernels": "benchmarks.kernels_bench",
    "encoder": "benchmarks.encoder_bench",
    "serving": "benchmarks.serving_bench",
    "index": "benchmarks.index_bench",
    "fig3": "benchmarks.fig3_scaling",
    "roofline": "benchmarks.roofline_report",
}


def _check_schema(suite: str, module) -> None:
    """Every key the driver declares must have been emitted, must map
    to a scheme-conformant registry name (``repro_bench_*_us``), and —
    when the obs layer is live — must actually be present in the
    registry (emit() mirrors every row there)."""
    from benchmarks import common
    from repro import obs
    expected_keys = getattr(module, "expected_keys", None)
    if expected_keys is None:
        return
    expected = list(expected_keys())
    emitted = set(common.EMITTED)
    missing = [k for k in expected if k not in emitted]
    if missing:
        raise RuntimeError(
            f"suite {suite!r} finished without emitting expected "
            f"result keys {missing} — a silently-empty benchmark is a "
            "failure, not a pass")
    bad = [k for k in expected
           if not obs.valid_metric_name(common.metric_name(k))]
    if bad:
        raise RuntimeError(
            f"suite {suite!r} declares row names {bad} that do not map "
            "onto the repro_<subsystem>_<metric> registry scheme")
    if obs.enabled():
        gauges = obs.snapshot(prefix="repro_bench")["gauges"]
        names = {g.split("{")[0] for g in gauges}
        lost = [k for k in expected
                if common.metric_name(k) not in names]
        if lost:
            raise RuntimeError(
                f"suite {suite!r} rows {lost} never reached the "
                "metrics registry — emit() and the registry disagree")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of " + ",".join(SUITES))
    ap.add_argument("--quick", action="store_true",
                    help="tiny graphs, minimal iters: exercises every "
                         "chosen driver end-to-end in seconds (the "
                         "`make bench-smoke` CI gate), numbers are NOT "
                         "meaningful measurements")
    ap.add_argument("--shards", type=int, default=None,
                    help="shard count for the serving suite's "
                         "partitioned-engine rows (default 2)")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import common
    if args.quick:
        common.QUICK = True
    if args.shards is not None:
        common.SHARDS = max(1, args.shards)
    chosen = args.only.split(",") if args.only else list(SUITES)

    print("name,us_per_call,derived")
    failures = []
    for suite in chosen:
        try:
            if suite not in SUITES:
                raise ValueError(f"unknown suite {suite}")
            module = importlib.import_module(SUITES[suite])
            common.EMITTED.clear()
            module.run()
            _check_schema(suite, module)
        except Exception:
            traceback.print_exc()
            failures.append(suite)
    if failures:
        print(f"# FAILED suites: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
