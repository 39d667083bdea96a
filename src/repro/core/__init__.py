"""repro.core — GEE algorithm internals.

DEPRECATED as a call-site API: new code should go through the unified
front door, ``repro.encoder.Embedder`` (backend selection, plan
caching, owned projection weights).  The per-strategy functions below
remain the backend *internals* and are re-exported here (lazily, PEP
562) for backward compatibility:

    gee_refine, gee_streaming, gee_apply_delta, gee_dense_oracle,
    make_w                      <- repro.core.gee
    gee_distributed, edge_mesh  <- repro.core.distributed
    gee_numpy, gee_python       <- repro.core.ref_python

(`repro.core.gee` stays the submodule — the function is
`repro.core.gee.gee` — so `from repro.core import gee as G` keeps its
historical module meaning.)
"""
from __future__ import annotations

import importlib

_FORWARDS = {
    "gee_refine": "repro.core.gee",
    "gee_streaming": "repro.core.gee",
    "gee_apply_delta": "repro.core.gee",
    "gee_dense_oracle": "repro.core.gee",
    "make_w": "repro.core.gee",
    "gee_distributed": "repro.core.distributed",
    "edge_mesh": "repro.core.distributed",
    "gee_numpy": "repro.core.ref_python",
    "gee_python": "repro.core.ref_python",
}

__all__ = sorted(_FORWARDS)


def __getattr__(name: str):
    if name in _FORWARDS:
        return getattr(importlib.import_module(_FORWARDS[name]), name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
