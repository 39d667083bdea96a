"""Device trace: capture with the JAX profiler, reduce to plain event
lists, and compute busy time, idle share, kernel time and the
breakdown from them.

A reduced trace is a dict, also what the tests' recorded fixture
holds::

    {"window": [t0_ns, t1_ns],               # the bench.window annotation
     "ops": {device: [[name, start_ns, dur_ns], ...]},      # XLA Ops
     "modules": {device: [[name, start_ns, dur_ns], ...]},  # XLA Modules
     "host": [[name, start_ns, dur_ns], ...]}  # bench.* annotations

Device and host events share the profile's clock.  A kernel is found by
its stable name: the HLO instruction that a `jax.jit`-wrapped
`pallas_call` becomes carries the wrapper's name
(`%gee_scatter_pallas.1 = ... custom-call(...)`).
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
from contextlib import contextmanager

WINDOW = "bench.window"
_INSTR = re.compile(r"^%?([A-Za-z0-9_\-]+?)(?:\.\d+)?\s*=")
_MODULE = re.compile(r"^(.*?)(?:\(\d+\))?$")


@contextmanager
def capture(enabled: bool):
    """Profile the body when enabled; yields a dict that receives the
    reduced trace (`out["trace"]`) once the body has run."""
    out = {}
    if not enabled:
        yield out
        return
    import jax
    d = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(d)
        try:
            with jax.profiler.TraceAnnotation(WINDOW):
                yield out
        finally:
            jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise RuntimeError("the profiler wrote no trace")
        out["trace"] = reduce_xspace(paths[0])
    finally:
        shutil.rmtree(d, ignore_errors=True)


def reduce_xspace(path: str) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, host = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU"):
            for line in plane.lines:
                dst = {"XLA Ops": ops, "XLA Modules": modules}.get(
                    line.name)
                if dst is not None:
                    dst[plane.name] = [[e.name, e.start_ns, e.duration_ns]
                                       for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.duration_ns]
                            for e in line.events
                            if e.name.startswith("bench."))
    win = [e for e in host if e[0] == WINDOW]
    if not win:
        raise RuntimeError("the trace has no bench.window annotation")
    t0, dur = win[0][1], win[0][2]
    return {"window": [t0, t0 + dur], "ops": ops, "modules": modules,
            "host": [e for e in host if e[0] != WINDOW]}


def instr_name(op_name: str) -> str:
    """`%gee_scatter_pallas.1 = f32[...] custom-call(...)` ->
    `gee_scatter_pallas`."""
    m = _INSTR.match(op_name)
    return m.group(1) if m else op_name.split(" ", 1)[0]


def _clip(events, t0, t1):
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            yield name, a, b


def union(intervals):
    """Merged [a, b) intervals, sorted."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def window_s(tr) -> float:
    t0, t1 = tr["window"]
    return (t1 - t0) / 1e9


def busy_s(tr) -> float:
    """Seconds in the window in which an op ran, averaged over the
    devices that ran any."""
    t0, t1 = tr["window"]
    per = [sum(b - a for a, b in union((a, b) for _, a, b in
                                       _clip(evs, t0, t1)))
           for evs in tr["ops"].values()]
    per = [p for p in per if p > 0]
    return sum(per) / len(per) / 1e9 if per else 0.0


def idle_share(tr) -> float:
    w = window_s(tr)
    return 1.0 - busy_s(tr) / w if w > 0 else float("nan")


def kernel_events(tr, kernel: str):
    """(start, end) of every op of the named kernel in the window."""
    t0, t1 = tr["window"]
    return [(a, b) for evs in tr["ops"].values()
            for name, a, b in _clip(evs, t0, t1)
            if instr_name(name) == kernel]


def kernel_s(tr, kernel: str) -> float:
    return sum(b - a for a, b in kernel_events(tr, kernel)) / 1e9


def ops_s(tr) -> float:
    """Summed op time in the window (all devices)."""
    t0, t1 = tr["window"]
    return sum(b - a for evs in tr["ops"].values()
               for _, a, b in _clip(evs, t0, t1)) / 1e9


def _module_at(mods, t):
    for name, s, d in mods:
        if s <= t < s + d:
            return _MODULE.match(name).group(1)
    return "?"


def breakdown(tr, top: int = 10) -> dict:
    """The device ops that took most time (module:instruction), and the
    idle gaps summed by the innermost bench annotation that covers
    most of each gap."""
    t0, t1 = tr["window"]
    per_op = {}
    for dev, evs in tr["ops"].items():
        mods = sorted(tr["modules"].get(dev, []), key=lambda e: e[1])
        for name, a, b in _clip(evs, t0, t1):
            key = f"{_module_at(mods, a)}:{instr_name(name)}"
            per_op[key] = per_op.get(key, 0) + (b - a)
    busy = union((a, b) for evs in tr["ops"].values()
                 for _, a, b in _clip(evs, t0, t1))
    gaps, prev = [], t0
    for a, b in busy:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    if t1 > prev:
        gaps.append((prev, t1))
    host = list(_clip(tr["host"], t0, t1))
    per_gap = {}
    for ga, gb in gaps:
        best, key = None, "host.unannotated"
        for name, a, b in host:
            ov = min(b, gb) - max(a, ga)
            if ov <= 0:
                continue
            rank = (ov, -(b - a))
            if best is None or rank > best:
                best, key = rank, name
        per_gap[key] = per_gap.get(key, 0) + (gb - ga)

    def top_of(d):
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": top_of(per_op), "idle_gaps": top_of(per_gap)}
