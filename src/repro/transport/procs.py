"""Worker subprocess lifecycle: spawn, handshake, pinned env, teardown.

The router spawns each shard/replica as ``python -m
repro.transport.worker`` with port 0 and learns the real address from
the worker's one-line stdout handshake (``LISTENING <addr>``).

**Environment pinning** (the config-divergence guard): a worker that
inherited a different ``REPRO_OBS`` / plan-cache / device config than
the router would silently produce different metrics, different cache
behavior, or even run on a different backend.  :func:`worker_env`
therefore stamps the router's *effective* state into the child env —
``REPRO_OBS`` from `obs.enabled()` (not the raw env: the router may
have called ``obs.configure``), ``REPRO_PLAN_CACHE`` and
``JAX_PLATFORMS`` passed through verbatim when set — and prepends the
live ``repro`` package's source root to ``PYTHONPATH`` so the child
resolves the same code regardless of how the parent was launched.

Spawn is two-phase (``wait=False`` + :meth:`WorkerProc.handshake`) so a
router bringing up N workers pays one jax-import latency, not N.

One process per chip: on a TPU host, spawning refuses with a clear
error (:func:`refuse_on_tpu`) instead of starting workers that would
contend for the chip the router holds.
"""
from __future__ import annotations

import os
import subprocess
import sys
import threading
from typing import Optional

import repro
from repro import obs
from repro.transport.errors import TransportError

#: env vars forwarded verbatim when set in the router's process
_FORWARD = ("REPRO_PLAN_CACHE", "JAX_PLATFORMS", "XLA_FLAGS",
            "REPRO_TRANSPORT_BACKEND")


def worker_env() -> dict:
    """Child environment with the router's effective config pinned."""
    env = os.environ.copy()
    env["REPRO_OBS"] = "on" if obs.enabled() else "off"
    for key in _FORWARD:
        val = os.environ.get(key)
        if val is not None:
            env[key] = val
    # repro may be a namespace package (__file__ is None): locate the
    # source root from __path__ instead
    pkg_dir = (os.path.dirname(repro.__file__) if repro.__file__
               else list(repro.__path__)[0])
    src_root = os.path.dirname(os.path.abspath(pkg_dir))
    parts = [src_root] + [p for p in
                          env.get("PYTHONPATH", "").split(os.pathsep)
                          if p and p != src_root]
    env["PYTHONPATH"] = os.pathsep.join(parts)
    return env


class WorkerProc:
    """One spawned worker: the Popen handle plus its RPC address
    (None until :meth:`handshake` reads the LISTENING line)."""

    def __init__(self, proc: subprocess.Popen, role: str,
                 label: str):
        self.proc = proc
        self.role = role
        self.label = label
        self.addr: Optional[str] = None

    @property
    def pid(self) -> int:
        return self.proc.pid

    def alive(self) -> bool:
        return self.proc.poll() is None

    def handshake(self, timeout_s: float = 120.0) -> str:
        """Block until the worker prints ``LISTENING <addr>``; kills
        the child and raises `TransportError` on timeout or early
        exit.  Idempotent once the address is known."""
        if self.addr is not None:
            return self.addr
        timer = threading.Timer(timeout_s, self.proc.kill)
        timer.start()
        try:
            for raw in self.proc.stdout:
                line = raw.decode("utf-8", "replace").strip()
                if line.startswith("LISTENING "):
                    self.addr = line.split(" ", 1)[1]
                    return self.addr
        finally:
            timer.cancel()
        rc = self.proc.wait()
        raise TransportError(
            f"{self.label} exited (rc={rc}) before listening"
            + (" [handshake timeout]" if rc and rc < 0 else ""))

    def stop(self, timeout_s: float = 10.0) -> None:
        """Reap the child: wait briefly (the router normally sends
        ``__shutdown__`` first), then terminate, then kill."""
        if self.proc.poll() is None:
            try:
                self.proc.wait(timeout=timeout_s)
            except subprocess.TimeoutExpired:
                self.proc.terminate()
                try:
                    self.proc.wait(timeout=5.0)
                except subprocess.TimeoutExpired:
                    self.proc.kill()
                    self.proc.wait()
        if self.proc.stdout is not None:
            self.proc.stdout.close()

    def kill(self) -> None:
        """Hard-kill (the fault-injection tests' crash lever)."""
        self.proc.kill()
        self.proc.wait()
        if self.proc.stdout is not None:
            self.proc.stdout.close()


def refuse_on_tpu() -> None:
    """Spawned workers cannot share this host's TPU: a chip belongs to
    one process, and the router, which has already touched JAX, holds
    it — a worker would fail or hang at its first device call, and
    pinning workers to the CPU would quietly serve from the wrong
    device.  Refuse before anything starts, until each worker is given
    a chip of its own."""
    import jax
    if jax.default_backend() == "tpu":
        raise TransportError(
            "socket transport cannot spawn shard or replica workers on "
            "a TPU host: the router process holds the chip and a worker "
            "would contend for it; use transport='local' (in-process "
            "shards), or run the router with JAX_PLATFORMS=cpu")


def _spawn(cmd: list, role: str, label: str, *,
           wait: bool, timeout_s: float) -> WorkerProc:
    refuse_on_tpu()
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            env=worker_env())
    wp = WorkerProc(proc, role, label)
    if obs.enabled():
        obs.counter("repro_transport_workers_spawned_total", role=role)
    if wait:
        wp.handshake(timeout_s)
    return wp


def spawn_shard_worker(shard_id: int, lo: int, hi: int, *, K: int,
                       n: int, chunk_size: int = 1 << 20,
                       backend: str = "streaming", plan_cache="auto",
                       addr: str = "127.0.0.1:0", wait: bool = True,
                       timeout_s: float = 120.0) -> WorkerProc:
    cmd = [sys.executable, "-m", "repro.transport.worker",
           "--role", "shard", "--addr", addr,
           "--shard-id", str(shard_id), "--lo", str(lo),
           "--hi", str(hi), "--classes", str(K), "--nodes", str(n),
           "--chunk-size", str(chunk_size), "--backend", backend,
           "--plan-cache", "off" if plan_cache is None
           else str(plan_cache)]
    return _spawn(cmd, "shard", f"shard worker {shard_id}",
                  wait=wait, timeout_s=timeout_s)


def spawn_replica_worker(data_dir: str, *, poll_ms: float = 20.0,
                         chunk_size: int = 1 << 20,
                         backend: str = "streaming", plan_cache="auto",
                         addr: str = "127.0.0.1:0", wait: bool = True,
                         timeout_s: float = 120.0) -> WorkerProc:
    cmd = [sys.executable, "-m", "repro.transport.worker",
           "--role", "replica", "--addr", addr,
           "--data-dir", str(data_dir), "--poll-ms", str(poll_ms),
           "--chunk-size", str(chunk_size), "--backend", backend,
           "--plan-cache", "off" if plan_cache is None
           else str(plan_cache)]
    return _spawn(cmd, "replica", f"replica worker @ {data_dir}",
                  wait=wait, timeout_s=timeout_s)
