"""One run of one cell: set up, measure a window, check the outputs
against the plain reference, and print the result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object; the numbers
compared, each beside its limit, are the last lines of standard error
and the result's last key.  A run that finds no TPU, or fewer chips
than the cell asks for, prints no result and exits 3.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

from yardstick.cells import BENCH, Cell

CACHE_DIR = os.path.join(BENCH, ".jax_cache")


class Context:
    """What a per-layer metric's reader (`bench/metrics/<name>.py`,
    `read(ctx) -> float | None`) may read."""

    def __init__(self, cell, records, delta, trace, peaks):
        self.cell = cell
        self.records = records      # the driver's window records
        self.delta = delta          # repro.obs registry deltas
        self.trace = trace          # reduced device trace
        self.peaks = peaks          # the chip's Peaks


def _enable_compile_cache(jax) -> None:
    """JAX's persistent cache at a fixed path inside the checkout, so
    only a cell's first run there compiles; every program is kept,
    however quick its compile."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(jax) -> dict:
    devs = jax.devices()
    peak = 0
    for d in devs:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def _finite(x):
    return x is not None and isinstance(x, (int, float)) and \
        math.isfinite(x)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t0: float, require_chip: bool = True,
             sizes: dict | None = None, out=sys.stdout, err=sys.stderr) -> int:
    cell = Cell(name)
    import jax
    _enable_compile_cache(jax)
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu"
                         or len(devs) < cell.chips):
        print(f"bench: {name} needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=err)
        return 3
    from repro import obs
    from yardstick import registry, trace as tr
    from yardstick.peaks import peaks

    drv = cell.driver().Driver(cell, seed, seconds, sizes)
    drv.setup()
    setup_s = time.perf_counter() - t0
    before = obs.registry().snapshot()
    with tr.capture(trace) as cap:
        res = drv.window()
    after = obs.registry().snapshot()
    device = device_info(jax)
    drv.release()
    numbers = drv.numbers()
    drv.close()

    compared = {k: {"value": numbers[k], "limit": cell.limits[k]}
                for k in cell.limits}
    correct = all(_finite(c["value"]) and c["value"] <= c["limit"]
                  for c in compared.values())
    result = {"correct": correct, "attempted": res["attempted"],
              "failed": res["failed"]}
    if not trace:
        vals = dict(res["metrics"], setup_s=setup_s)
        result["metrics"] = {m["name"]: {"value": vals[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    else:
        t = cap["trace"]
        pk = peaks(device["kind"]) if devs[0].platform == "tpu" else None
        ctx = Context(cell, drv.records, registry.Delta(before, after),
                      t, pk)
        metrics = {}
        for m in cell.per_layer:
            v = cell.reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        device.update(busy_s=tr.busy_s(t), window_s=tr.window_s(t))
        result["breakdown"] = tr.breakdown(t)
    result["device"] = device
    result["compared"] = compared
    for k, c in compared.items():
        print(f"compared {k} = {c['value']!r} (limit {c['limit']!r})",
              file=err)
    err.flush()
    print(json.dumps(result), file=out, flush=True)
    return 0


def main(argv=None, *, t0: float) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    return run_cell(a.workload, a.seed, a.seconds, bool(a.trace), t0=t0)
