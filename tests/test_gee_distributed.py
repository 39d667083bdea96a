"""Distributed GEE equivalence on 8 host devices (subprocess so the
device-count flag never leaks into other tests)."""
import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow          # fresh-interpreter device sweep

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import json
import numpy as np, jax, jax.numpy as jnp
from repro.graph.generators import erdos_renyi, powerlaw
from repro.graph.edges import Graph, make_labels
from repro.core import ref_python as R
from repro.core.distributed import gee_distributed, edge_mesh

out = {"devices": len(jax.devices())}
rng = np.random.default_rng(0)
mesh = edge_mesh()
# hub: node 0 on every edge, the fullest destination tile on every chip;
# sparse: fewer edges than one chip's edge block, so most tiles are
# padding; dup: every edge four times
ends = rng.integers(1, 600, 5000).astype(np.int32)
hub = Graph(np.zeros(5000, np.int32), ends,
            (rng.random(5000) + 0.5).astype(np.float32), 600)
d = erdos_renyi(400, 1500, seed=4, weighted=True)
dup = Graph(np.tile(d.u, 4), np.tile(d.v, 4), np.tile(d.w, 4), d.n)
graphs = {"er": erdos_renyi(1003, 20007, seed=1, weighted=True),
          "skew": powerlaw(512, 8192, seed=2),
          "hub": hub,
          "sparse": erdos_renyi(300, 100, seed=5, weighted=True),
          "dup": dup}
for name, g in graphs.items():
    for K in (1, 7, 50):
        Y = make_labels(g.n, K, 0.2, rng)
        Zref = R.gee_numpy(g.u, g.v, g.w, Y, K, g.n)
        Z = gee_distributed(g, Y, K=K, mesh=mesh)
        out[f"{name}_{K}_err"] = float(np.abs(Z - Zref).max())
        out[f"{name}_{K}_shape"] = list(Z.shape) == [g.n, K]
# Laplacian scaling
from repro.core.gee import gee
for name, g in [("er", erdos_renyi(500, 6000, seed=3, weighted=True)),
                ("skew", graphs["skew"])]:
    Y = make_labels(g.n, 5, 0.3, rng)
    Zl_ref = np.asarray(gee(jnp.asarray(g.u), jnp.asarray(g.v),
                            jnp.asarray(g.w), jnp.asarray(Y), K=5, n=g.n,
                            laplacian=True))
    Zl = gee_distributed(g, Y, K=5, mesh=mesh, laplacian=True)
    out[f"laplacian_{name}_err"] = float(np.abs(Zl - Zl_ref).max())
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def dist_results():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


def test_runs_on_8_devices(dist_results):
    assert dist_results["devices"] == 8


@pytest.mark.parametrize("K", [1, 7, 50])
@pytest.mark.parametrize("graph", ["er", "skew", "hub", "sparse", "dup"])
def test_matches_serial(dist_results, graph, K):
    """`gee_distributed` against the serial oracle: a hub's full tile,
    mostly-padding tiles, repeated edges; K = 1 and 7 round up to the
    kernel's 8 columns, K = 50 is the benchmark's."""
    assert dist_results[f"{graph}_{K}_err"] < 1e-4
    assert dist_results[f"{graph}_{K}_shape"]


@pytest.mark.parametrize("graph", ["er", "skew"])
def test_laplacian(dist_results, graph):
    assert dist_results[f"laplacian_{graph}_err"] < 1e-4
