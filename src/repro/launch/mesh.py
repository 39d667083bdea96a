"""Production meshes.

A v5e pod is 16x16 = 256 chips; the multi-pod mesh stacks pods on a
leading pure-DP axis (cross-pod traffic is gradient all-reduce only, so
adding pods never changes the per-pod program — the elasticity story).

Defined as functions, not module constants: importing this module must
never touch jax device state (the dry-run sets XLA_FLAGS first).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes, devices=None):
    """`jax.make_mesh` with Auto axes: the programs here are sharded by
    annotation and propagation, not by the Explicit-axes typing that
    `make_mesh` defaults to."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    import numpy as np
    need = int(np.prod(shape))
    return _make_mesh(shape, axes, devices=jax.devices()[:need])


def make_gee_mesh(*, multi_pod: bool = False):
    """GEE runs edge-parallel over every chip: flat 1-D mesh."""
    n = 512 if multi_pod else 256
    return _make_mesh((n,), ("edges",), devices=jax.devices()[:n])


def make_host_mesh():
    """Whatever devices exist (tests / CPU): 1-D data mesh."""
    n = len(jax.devices())
    return _make_mesh((n,), ("data",))
