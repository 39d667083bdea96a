"""A tiny CPU rehearsal of the four-chip cell `er-4chip.refit` through
the harness, on four virtual CPU devices (a subprocess, so the device
count never leaks into other tests): `auto` resolves to
`distributed:reduce_scatter`, the runs end `correct`, the warmed-up
window compiles nothing, and a run whose chips leave out the exchange
of their partial Z is caught."""
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH

CELL = "er-4chip.refit"
#: the cell's mean degree (2s/n = 218) at n = 4,000
SIZES = {"n": 4000, "s": 436000}

SCRIPT = r"""
import io, json, sys, time
sys.path[:0] = [sys.argv[1], sys.argv[2]]
import jax, jax.numpy as jnp
from yardstick.harness import run_cell

def run(trace):
    out, err = io.StringIO(), io.StringIO()
    rc = run_cell(%(cell)r, 987654321987, 1.5, trace, t0=time.perf_counter(),
                  require_chip=False, sizes=%(sizes)r, out=out, err=err)
    lines = out.getvalue().strip().splitlines()
    return {"rc": rc, "res": json.loads(lines[-1]),
            "err": err.getvalue()[-2000:]}

res = {"devices": len(jax.devices()), "untraced": run(False),
       "traced": run(True)}

# the fault: each chip keeps its own rows of its partial Z, unsummed
from repro.core import distributed as D
def local_rows(u, v, w, Y, class_w, *, K, n, p):
    Z = D._scatter_rows(n, K, *D._contributions(u, v, w, Y, class_w))
    me = jax.lax.axis_index(D.AXIS)
    return (jax.lax.dynamic_slice_in_dim(Z, me * (n // p), n // p),
            jnp.zeros((), jnp.int32))
D._body_reduce_scatter = local_rows
D._program.cache_clear()
res["no_exchange"] = run(False)
print("RESULT " + json.dumps(res))
"""


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    src = os.path.join(os.path.dirname(BENCH), "src")
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT % {"cell": CELL, "sizes": SIZES},
         BENCH, src], env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


def test_four_devices(runs):
    assert runs["devices"] == 4


@pytest.mark.parametrize("mode", ["untraced", "traced"])
def test_runs_correct(runs, mode):
    run = runs[mode]
    assert run["rc"] == 0, run["err"]
    res = run["res"]
    assert res["correct"] is True, res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["device"]["count"] == 4
    assert list(res)[-1] == "compared"


def test_untraced_reports_end_to_end(runs):
    assert set(runs["untraced"]["res"]["metrics"]) == {
        "embed_edges_per_s", "setup_s"}


def test_traced_reports_counters(runs):
    """The program counters' metrics read on the CPU (the trace's
    readers need a TPU plane): every slot held a contribution (the edge
    list pads nothing over four devices), about 10% of them from a
    labeled donor, and the window compiled nothing."""
    m = {k: v["value"] for k, v in runs["traced"]["res"]["metrics"].items()}
    assert m["window_compiles"] == 0
    assert m["slot_fill"] == pytest.approx(100.0)
    assert 5 < m["useful_fill"] < 20


def test_missing_exchange_is_caught(runs):
    run = runs["no_exchange"]
    assert run["rc"] == 0, run["err"]
    assert run["res"]["correct"] is False, run["res"]["compared"]
