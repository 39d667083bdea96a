import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=512")
"""§Perf hillclimbing driver: run tagged optimization variants of the
three chosen cells and print before/after roofline terms.

The three pairs (selection rationale in EXPERIMENTS.md §Perf):
  1. qwen1.5-110b x train_4k   — worst memory blow-up (biggest dense)
  2. grok-1-314b  x train_4k   — most collective-bound
  3. gee-friendster (reduce_scatter) — the paper's own workload

Each variant re-lowers the cell with one change and records the probe
terms under a tag; compare with
    PYTHONPATH=src python -m repro.launch.hillclimb --list
"""
import argparse

from repro.launch.dryrun import run_cell, run_gee

VARIANTS = {
    # --- qwen110 train: memory term ------------------------------------
    "qwen110-base": dict(kind="cell", arch="qwen1.5-110b",
                         shape="train_4k", kw={}),
    "qwen110-tri": dict(kind="cell", arch="qwen1.5-110b", shape="train_4k",
                        kw=dict(impl="triangular", tag="tri")),
    "qwen110-accum8": dict(kind="cell", arch="qwen1.5-110b",
                           shape="train_4k",
                           kw=dict(accum_steps=8, tag="accum8")),
    "qwen110-seqshard": dict(kind="cell", arch="qwen1.5-110b",
                             shape="train_4k",
                             kw=dict(seq_shard_acts=True, tag="seqshard")),
    "qwen110-tri-accum8": dict(kind="cell", arch="qwen1.5-110b",
                               shape="train_4k",
                               kw=dict(impl="triangular", accum_steps=8,
                                       tag="tri-accum8")),
    "qwen110-accum8-seqshard": dict(
        kind="cell", arch="qwen1.5-110b", shape="train_4k",
        kw=dict(accum_steps=8, seq_shard_acts=True,
                tag="accum8-seqshard")),
    "qwen110-accum16-seqshard": dict(
        kind="cell", arch="qwen1.5-110b", shape="train_4k",
        kw=dict(accum_steps=16, seq_shard_acts=True,
                tag="accum16-seqshard")),
    # prefill cell where attention flops dominate: triangular matters
    "qwen110-prefill-base": dict(kind="cell", arch="qwen1.5-110b",
                                 shape="prefill_32k", kw={}),
    "qwen110-prefill-tri": dict(kind="cell", arch="qwen1.5-110b",
                                shape="prefill_32k",
                                kw=dict(impl="triangular", tag="tri")),
    "grok-seqshard": dict(kind="cell", arch="grok-1-314b",
                          shape="train_4k",
                          kw=dict(seq_shard_acts=True, tag="seqshard")),
    # --- grok train: collective term ------------------------------------
    "grok-base": dict(kind="cell", arch="grok-1-314b", shape="train_4k",
                      kw={}),
    "grok-tri": dict(kind="cell", arch="grok-1-314b", shape="train_4k",
                     kw=dict(impl="triangular", tag="tri")),
    "grok-nofsdp": dict(kind="cell", arch="grok-1-314b", shape="train_4k",
                        kw=dict(fsdp=False, tag="nofsdp")),
    "grok-accum8": dict(kind="cell", arch="grok-1-314b", shape="train_4k",
                        kw=dict(accum_steps=8, tag="accum8")),
    "grok-int8": dict(kind="cell", arch="grok-1-314b", shape="train_4k",
                      kw=dict(compress_grads=True, tag="int8")),
    "grok-tri-accum8": dict(kind="cell", arch="grok-1-314b",
                            shape="train_4k",
                            kw=dict(impl="triangular", accum_steps=8,
                                    tag="tri-accum8")),
    # --- GEE friendster: the paper's workload ---------------------------
    "gee-rs": dict(kind="gee"),
    # --- kernel-geometry autotune (repro.launch.autotune): coordinate
    # descent over TILE_N/EDGE_BLOCK (scatter) and block_rows (fused
    # top-k), reporting achieved-vs-roofline HBM bandwidth.  Run with
    # XLA_FLAGS=--xla_force_host_platform_device_count=1 on CPU (this
    # module's 512-device default exists for the SPMD dry runs and
    # only slows single-kernel timing).
    "gee-scatter-tune": dict(kind="kernel", fn="scatter"),
    "gee-topk-tune": dict(kind="kernel", fn="topk"),
}

#: --quick workload shrink for the kernel tuners (bench-smoke lane:
#: exercise the whole descent + bandwidth report in seconds)
_KERNEL_QUICK = {
    "scatter": dict(n=1_000, s=8_000, K=8,
                    space={"tile_n": (64, 128),
                           "edge_block": (128, 256)}, iters=1),
    "topk": dict(m=2_000, K=8, nq=16, k=5,
                 space={"block_rows": (256, 1024)}, iters=1),
}


def _run_kernel_tune(fn: str, quick: bool) -> None:
    from repro.launch.autotune import tune_scatter, tune_topk
    tuner = {"scatter": tune_scatter, "topk": tune_topk}[fn]
    kw = _KERNEL_QUICK[fn] if quick else {}
    out = tuner(**kw)
    frac = out["roofline_frac"]
    share = "not measured" if frac is None else f"{frac * 100:.2f}%"
    print(f"best[{fn}]: {out['best']}  {out['seconds'] * 1e3:.2f} ms  "
          f"{out['achieved_gbps']:.2f} GB/s "
          f"(roofline share {share}, {out['mode']} mode)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("variant", nargs="*", help=list(VARIANTS))
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--quick", action="store_true",
                    help="tiny kernel-tune workloads (bench-smoke lane)")
    args = ap.parse_args()
    if args.list or not args.variant:
        for k in VARIANTS:
            print(k)
        return
    for name in args.variant:
        v = VARIANTS[name]
        if v["kind"] == "gee":
            run_gee()
        elif v["kind"] == "kernel":
            _run_kernel_tune(v["fn"], args.quick)
        else:
            run_cell(v["arch"], v["shape"], **v["kw"])


if __name__ == "__main__":
    main()
