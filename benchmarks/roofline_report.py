"""Roofline report: reads the dry-run artifacts (launch/dryrun.py must
have run) and emits the per-cell terms + memory-bound verdict (C6).

The paper's finding "the workload is memory bound; atomics are free"
maps here to: for the GEE cells, memory_s and collective_s dominate
compute_s by orders of magnitude — quantified below.
"""
from __future__ import annotations

import json
import os

from benchmarks.common import emit

ART = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "artifacts", "dryrun")


def run() -> None:
    if not os.path.isdir(ART):
        emit("roofline/NO_ARTIFACTS", 0.0,
             "run python -m repro.launch.dryrun --all first")
        return
    for mesh_name in sorted(os.listdir(ART)):
        d = os.path.join(ART, mesh_name)
        for fn in sorted(os.listdir(d)):
            if not fn.endswith(".json"):
                continue
            rec = json.load(open(os.path.join(d, fn)))
            cell = fn[:-5].replace("__", "/")
            step = max(rec.get("compute_s", 0), rec.get("memory_s", 0),
                       rec.get("collective_s", 0))
            probed = "probe" in rec
            # multi-pod cells are compile-proof only (no depth probes):
            # their raw flops are scan-undercounted, so MFU is not
            # meaningful there — flagged instead of printed.
            mfu = (f"mfu={rec.get('mfu', 0):.4f}" if probed
                   else "mfu=n/a(unprobed)")
            emit(f"roofline/{mesh_name}/{cell}", step,
                 f"dom={rec.get('dominant')};"
                 f"compute={rec.get('compute_s', 0):.3e};"
                 f"memory={rec.get('memory_s', 0):.3e};"
                 f"coll={rec.get('collective_s', 0):.3e};{mfu}")
    # C6: GEE memory-bound check
    for mesh_name in sorted(os.listdir(ART)):
        p = os.path.join(ART, mesh_name, "gee__reduce_scatter.json")
        if os.path.exists(p):
            rec = json.load(open(p))
            ratio = (max(rec["memory_s"], rec["collective_s"])
                     / max(rec["compute_s"], 1e-18))
            emit(f"roofline/{mesh_name}/gee_memory_over_compute", ratio,
                 "C6: paper says memory-bound; ratio >> 1 confirms")


if __name__ == "__main__":
    run()
