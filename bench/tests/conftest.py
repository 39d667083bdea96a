"""Shared helpers for the benchmark's own tests (run on the CPU:
`JAX_PLATFORMS=cpu python -m pytest bench/tests -q`)."""
import io
import json
import os
import sys
import time

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

#: the CPU rehearsal's cut of every cell: the cells' mean degree
#: (2s/n = 37.5) at n = 2,000
TINY = {"n": 2000, "s": 37500}


def run_tiny(cell: str, *, seed: int = 987654321987, seconds: float = 1.5,
             trace: bool = False):
    """One CPU run of `cell` through the harness, the chip check
    skipped; returns (exit code, result dict, stderr text)."""
    from yardstick.harness import run_cell
    out, err = io.StringIO(), io.StringIO()
    rc = run_cell(cell, seed, seconds, trace, t0=time.perf_counter(),
                  require_chip=False, sizes=dict(TINY),
                  out=out, err=err)
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), err.getvalue()


def cell_names(driver: str | None = None) -> list:
    """The cells in BENCHMARK.json, those run by `driver` where given."""
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        names = [w["name"] for w in json.load(f)["workloads"]]
    if driver is None:
        return names
    out = []
    for name in names:
        with open(os.path.join(BENCH, "workloads", name + ".json")) as f:
            if json.load(f)["driver"] == driver:
                out.append(name)
    return out


@pytest.fixture
def cells():
    return cell_names()
