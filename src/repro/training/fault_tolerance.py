"""Fault tolerance & elasticity for 1000+-node runs.

What a real multi-pod deployment needs and how this repo provides it:

1. Crash recovery — atomic checkpoints + `restore_checkpoint`
   (checkpoint.py); the train loop periodically saves params+opt+data
   state and resumes from LATEST on restart.  Tested by killing a
   training subprocess mid-run (tests/test_checkpoint.py).

2. Node failure / elastic re-mesh — `ElasticMeshManager` rebuilds the
   mesh from the surviving device list at the next checkpoint boundary
   and re-jits the step.  Because checkpoints are stored UNSHARDED
   (host npz) and shardings are derived from (mesh, logical rules),
   restoring onto a different device count is just `make_rules(new_mesh)`
   — no resharding pass needed.  The `pod` axis being pure-DP means a
   lost pod only changes the gradient denominator.

3. Straggler mitigation — `StragglerMonitor` tracks per-step wall
   times; a step exceeding `deadline_factor` x the trailing median is
   logged and counted.  On TPU pods the SPMD step is collectively
   synchronous, so mitigation = re-mesh without the slow host (policy
   hook `on_straggler`), plus data-time skipping for input stalls.

4. Heartbeats — `Heartbeat` files under the run dir let an external
   supervisor (or another pod) detect a dead host by mtime; this is the
   standard file-based liveness contract for batch schedulers.
"""
from __future__ import annotations

import collections
import json
import os
import statistics
import time
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
from jax.sharding import AxisType


class Heartbeat:
    def __init__(self, run_dir: str, host_id: int = 0,
                 interval_s: float = 10.0):
        self.path = os.path.join(run_dir, f"heartbeat_{host_id}")
        self.interval = interval_s
        self._last = 0.0
        os.makedirs(run_dir, exist_ok=True)

    def beat(self, step: int) -> None:
        now = time.time()
        if now - self._last >= self.interval:
            tmp = self.path + ".tmp"
            with open(tmp, "w") as f:
                json.dump({"step": step, "time": now}, f)
            os.replace(tmp, self.path)
            self._last = now

    @staticmethod
    def dead_hosts(run_dir: str, timeout_s: float = 60.0) -> list:
        now = time.time()
        dead = []
        for f in os.listdir(run_dir):
            if f.startswith("heartbeat_") and not f.endswith(".tmp"):
                if now - os.path.getmtime(os.path.join(run_dir, f)) > \
                        timeout_s:
                    dead.append(int(f.split("_")[1]))
        return dead


@dataclass
class StragglerMonitor:
    deadline_factor: float = 3.0
    window: int = 50
    on_straggler: Optional[Callable[[int, float, float], None]] = None
    times: collections.deque = field(
        default_factory=lambda: collections.deque(maxlen=50))
    straggler_steps: list = field(default_factory=list)

    def record(self, step: int, dt: float) -> bool:
        """Returns True if this step counts as a straggler."""
        is_straggler = False
        if len(self.times) >= 10:
            med = statistics.median(self.times)
            if dt > self.deadline_factor * med:
                is_straggler = True
                self.straggler_steps.append((step, dt, med))
                if self.on_straggler:
                    self.on_straggler(step, dt, med)
        self.times.append(dt)
        return is_straggler


class ElasticMeshManager:
    """Rebuild mesh/rules/step when the healthy device set changes.

    Works with any (pod, data, model)-style factorization: the model
    axis is preserved (weights must still fit), the data axes shrink to
    the largest multiple that the surviving devices support."""

    def __init__(self, build_step: Callable, model_axis_size: int):
        self.build_step = build_step
        self.model_axis = model_axis_size
        self.generation = 0

    def remesh(self, healthy_devices) -> tuple:
        n = len(healthy_devices)
        model = self.model_axis
        assert n >= model, "not enough devices for the model axis"
        data = n // model
        usable = data * model
        mesh = jax.make_mesh((data, model), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2,
                             devices=healthy_devices[:usable])
        self.generation += 1
        step = self.build_step(mesh)
        return mesh, step, self.generation


def simulate_failure(devices, kill: int):
    """Test hook: drop `kill` devices from the tail (a dead host)."""
    return devices[:len(devices) - kill]
