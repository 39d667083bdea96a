"""repro.encoder — the unified GEE embedding API (the one front door).

    from repro.encoder import Embedder, EncoderConfig

    emb = Embedder(EncoderConfig(K=5)).fit(graph, Y)   # backend="auto"
    Z   = emb.transform()
    emb.partial_fit(delta)        # exact O(batch) update
    emb.refit(new_Y)              # cached plan, no host re-packing

Backends (select by name, register new ones with `register_backend`):
numpy, xla, pallas, streaming, distributed:reduce_scatter — or "auto"
(the default), resolved at plan time from
(n, s, device kind, device count) via the overridable
`backends.AUTO_POLICY` table.  All produce the same Z (see
tests/test_encoder.py's cross-backend conformance suite); they differ
only in where the work runs.  The legacy per-strategy functions remain
as internals under `repro.core` / `repro.kernels`.

`fit`/`plan` accept a `repro.graph.sources.GraphSource` anywhere a
Graph is accepted; the source's content fingerprint keys the
persistent cross-process plan cache (`plan_cache.PlanDiskCache`,
REPRO_PLAN_CACHE to relocate or disable), so a fresh process embedding
a known graph skips host packing entirely.
"""
from repro.encoder.backends import (AUTO_POLICY, Backend, get_backend,
                                    list_backends, register_backend,
                                    resolve_auto)
from repro.encoder.config import EncoderConfig
from repro.encoder.embedder import Embedder, NotFittedError
from repro.encoder.plan import Plan
from repro.encoder.plan_cache import PlanDiskCache, default_cache

__all__ = ["AUTO_POLICY", "Backend", "Embedder", "EncoderConfig",
           "NotFittedError", "Plan", "PlanDiskCache", "default_cache",
           "get_backend", "list_backends", "register_backend",
           "resolve_auto"]
