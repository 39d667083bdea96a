"""Readings for a cell's correctness limits: the program's numbers and
the control's on many seeds, in one process (set-up is long, so the
compiled programs are shared across seeds).

    python3 bench/readings.py --workload <cell> --seeds 1,2,3 --seconds 3 \
        [--control-seeds 1,2,3] [--out readings.jsonl]

Each seed runs the cell's own set-up and a short window at the cell's
own load through the same driver as `run.py`, then prints one JSON line
with the numbers compared: "program" for the program's answers and,
for the control seeds, "control" for the plain reference put in the
program's place at the next precision below the configuration's
(`Precision.HIGH`, see `yardstick/ref.py`).  The benchmark's own runs
never run the control.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

from yardstick import harness  # noqa: E402
from yardstick.cells import Cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", default=None)
    a = ap.parse_args(argv)
    cell = Cell(a.workload)
    import jax
    harness._enable_compile_cache(jax)
    if jax.devices()[0].platform != "tpu":
        print("readings: no TPU found", file=sys.stderr)
        return 3
    seeds = [int(s) for s in a.seeds.split(",")]
    ctrl = {int(s) for s in a.control_seeds.split(",") if s}
    for seed in seeds:
        t = time.perf_counter()
        drv = cell.driver().Driver(cell, seed, a.seconds)
        drv.setup()
        t_setup = time.perf_counter() - t
        res = drv.window()
        drv.release()
        row = {"workload": a.workload, "seed": seed,
               "setup_s": t_setup, "window": res,
               "program": drv.numbers()}
        if seed in ctrl:
            row["control"] = drv.numbers(control=True)
        drv.close()
        del drv
        gc.collect()
        row["seed_s"] = time.perf_counter() - t
        line = json.dumps(row)
        print(line, flush=True)
        if a.out:
            with open(a.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
