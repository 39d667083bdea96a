"""Distributed GEE demo: embed a multi-million-edge graph through the
unified Embedder API on 8 (placeholder) devices, where `auto` resolves
to `distributed:reduce_scatter` — the code path the 512-chip dry-run
lowers, at laptop scale.  The plan (padding + packing each device's
edges on the devices) is built once; the timed refit reuses it.

    python examples/distributed_gee.py
"""
import os
import subprocess
import sys

WORKER = r"""
import time
import numpy as np, jax
from repro.graph.generators import sbm
from repro.graph.edges import make_labels
from repro.graph.partition import shuffle_edges
from repro.encoder import Embedder, EncoderConfig

n, K, s = 200_000, 50, 8_000_000
g, truth = sbm(n, K, s, p_in=0.85, seed=0)
g = shuffle_edges(g, seed=1)
Y = make_labels(n, K, 0.10, np.random.default_rng(0), true_labels=truth)
P = len(jax.devices())
print(f"devices={P} edges={s:,}")

emb = Embedder(EncoderConfig(K=K))                 # backend="auto"
emb.fit(g, Y)                                      # plan + warm compile
t0 = time.perf_counter()
emb.refit(Y)                                       # cached plan
jax.block_until_ready(emb.Z_)
dt = time.perf_counter() - t0
pred = emb.predict()
mask = Y < 0
acc = (pred[mask] == truth[mask]).mean()
print(f"backend={emb.backend.name} {dt*1e3:9.1f} ms  "
      f"({s/dt/1e6:6.1f} M edges/s)  "
      f"plan={emb.plan_stats}  unlabeled-acc={acc:.3f}")
"""


def main():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    r = subprocess.run([sys.executable, "-c", WORKER], env=env, text=True)
    sys.exit(r.returncode)


if __name__ == "__main__":
    main()
