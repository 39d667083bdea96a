"""Batch driver: `Embedder` with the default `auto` backend, re-embedding
one graph back to back over a window.

Cell parameters (`bench/workloads/<cell>.json`, key "traffic_params"):

    mode         "refit": each step re-embeds the graph under the next
                 of `label_sets` churned label vectors (the cached plan
                 is reused, so no step packs on the host).
    label_sets   how many churned label vectors to cycle.
    churn_frac   share of nodes revealed and of known labels flipped in
                 each vector.

Each step is fenced with `block_until_ready` and annotated
(`bench.step`).  What is compared: the Z of the window's last step
against the plain reference on the same graph and labels.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from yardstick import compare, gen, ref


class Driver:
    def __init__(self, cell, seed: int, seconds: float,
                 sizes: dict | None = None):
        c = dict(cell.config["graph"])
        c.update(sizes or {})
        self.n, self.s, self.K = int(c["n"]), int(c["s"]), int(c["K"])
        self.labeled_frac = float(c["labeled_frac"])
        self.params = cell.spec["traffic_params"]
        if self.params["mode"] != "refit":
            raise ValueError(f"unknown batch mode {self.params['mode']!r}")
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.records = {"n": self.n, "s": self.s, "K": self.K}

    # -- set-up -------------------------------------------------------------

    def setup(self) -> None:
        import jax
        from repro.encoder import Embedder, EncoderConfig
        from repro.graph.edges import Graph
        self._jax = jax
        self.u, self.v, self.w = gen.erdos_renyi(self.n, self.s, self.seed)
        truth = gen.true_labels(self.n, self.K,
                                np.random.default_rng([self.seed, 0]))
        Y = gen.make_labels(self.n, self.K, self.labeled_frac,
                            np.random.default_rng([self.seed, 1]),
                            true_labels=truth)
        self.labels = [gen.churn_labels(Y, truth, self.K,
                                        self.params["churn_frac"],
                                        np.random.default_rng(
                                            [self.seed, 2, i]))
                       for i in range(self.params["label_sets"])]
        # plan_cache=None: every seed is a new graph, so the on-disk plan
        # tier could only miss, and would write ~0.8 GB a run
        self.emb = Embedder(EncoderConfig(K=self.K), plan_cache=None)
        # warm the one shape the window uses, under labels that no step
        # uses, so a step that leaves Z as it was cannot match the
        # reference
        Y_warm = gen.churn_labels(Y, truth, self.K, 0.01,
                                  np.random.default_rng([self.seed, 2, 99]))
        self.emb.fit(Graph(self.u, self.v, self.w, self.n), Y_warm)
        jax.block_until_ready(self.emb.Z_)
        self.records["backend"] = self.emb.backend.name

    # -- window -------------------------------------------------------------

    def window(self) -> dict:
        ann = self._jax.profiler.TraceAnnotation
        steps, t0 = 0, time.perf_counter()
        step_s = []
        while time.perf_counter() - t0 < self.seconds:
            ts = time.perf_counter()
            with ann("bench.step"):
                self.emb.refit(self.labels[steps % len(self.labels)])
                self._jax.block_until_ready(self.emb.Z_)
            step_s.append(time.perf_counter() - ts)
            steps += 1
        elapsed = time.perf_counter() - t0
        self.last = (steps - 1) % len(self.labels)
        self.records.update(steps=steps, window_s=elapsed)
        print("step seconds: " + " ".join(f"{x:.4f}" for x in step_s),
              file=sys.stderr, flush=True)
        return {"attempted": steps, "failed": 0,
                "metrics": {"embed_edges_per_s": self.s * steps / elapsed}}

    # -- correctness --------------------------------------------------------

    def release(self) -> None:
        """Take the last step's Z to the host and free the program."""
        self.Z = np.asarray(self.emb.Z_)
        del self.emb

    def numbers(self, control: bool = False) -> dict:
        Y = self.labels[self.last]
        Zref = ref.gee(self.u, self.v, self.w, Y, self.K, self.n)
        if control:
            Z = ref.gee(self.u, self.v, self.w, Y, self.K, self.n,
                        precision="high").astype(np.float32)
        else:
            Z = self.Z
        return {"z_rel_err": compare.rel_err(Z, Zref)}

    def close(self) -> None:
        pass
