"""Embedder: the one front door for GEE.

    cfg = EncoderConfig(K=5)                  # backend="auto" resolves
    emb = Embedder(cfg).fit(source, Y)        # Graph or GraphSource
    Z   = emb.transform()                 # (n, K)
    emb.partial_fit(delta_graph)          # O(batch) exact update
    emb.refit(Y_new)                      # reuse the cached plan

Design rules:

* **Backend is configuration.**  Every execution strategy registered in
  `backends.py` is reachable by name; call sites never import a
  strategy-specific function again.  `backend="auto"` (the config
  default) resolves at plan time from (n, s, device kind, device count)
  via the `AUTO_POLICY` table.
* **plan() is a two-tier cache.**  Tier 1: O(1) array-identity match —
  refits and repeated fits on the same arrays skip all host work.
  Tier 2: a persistent on-disk cache keyed on the graph's CONTENT
  fingerprint (`repro.encoder.plan_cache`), so a fresh process
  (restart, CI rerun, new serving replica) embedding the same graph
  skips host packing too and only re-runs cheap device placement
  (`plan_stats` counts built / hits / disk_hits / disk_stores; the
  encoder benchmark measures both tiers).
* **The Embedder owns the projection weights.**  The class weights
  `class_weights(Y, K)` are computed once per embed and handed to the
  backend (pallas applies 1/n_k per column of Z, the others per
  contribution through `make_w`).  The per-node `Wv_` every subsequent
  `partial_fit` uses is built from them on first use, so the raw
  `gee_apply_delta` contract — "Wv must be the weights Z was built
  with" — can no longer be violated by a caller holding a stale or
  foreign Wv.
"""
from __future__ import annotations

import os
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

import functools

from repro import obs
from repro.core.gee import (class_weights, gee_apply_delta,
                            gee_apply_delta_owned, kmeans_refine_round,
                            make_w)
from repro.encoder.backends import Backend, get_backend, resolve_auto
from repro.encoder.config import EncoderConfig
from repro.encoder.plan import Plan, owned_contributions
from repro.encoder.plan_cache import PlanDiskCache, default_cache
from repro.graph.edges import Graph, bucket_size
from repro.graph.sources import as_graph


class NotFittedError(RuntimeError):
    pass


@functools.partial(jax.jit, static_argnames=("K", "kmeans_iters"))
def _kmeans_reassign(Z, labels, Y0, *, K: int, kmeans_iters: int):
    """Jitted wrapper over the shared `core.gee.kmeans_refine_round`."""
    return kmeans_refine_round(Z, labels, Y0, K, kmeans_iters)


class Embedder:
    """Unified GEE embedding API over pluggable backends.

    Fitted state (sklearn-style trailing underscore):
      Z_        (n, K) float32 embedding (device array).
      labels_   the labels Z was built under (int32, -1 = unknown).
      Wv_       per-node projection weights Z was built with (built
                from the class weights on first read).
    """

    def __init__(self, config: EncoderConfig, *,
                 backend: Optional[str] = None, mesh=None,
                 plan_cache: Union[str, PlanDiskCache, None] = "auto"):
        self.config = config
        spec = backend if backend is not None else config.backend
        self._backend_spec = spec
        #: resolved Backend; None until first plan() when spec="auto"
        self.backend: Optional[Backend] = (
            None if spec == "auto" else get_backend(spec))
        self.mesh = mesh
        if plan_cache == "auto":
            self.plan_cache = default_cache()
        elif plan_cache is None or plan_cache is False:
            self.plan_cache = None
        elif isinstance(plan_cache, (str, os.PathLike)):
            self.plan_cache = PlanDiskCache(plan_cache)
        else:
            self.plan_cache = plan_cache
        self._plan: Optional[Plan] = None
        self._deltas_applied = 0       # partial_fits since last _embed
        self._Yj = self._Yfit = None
        self.Z_: Optional[jnp.ndarray] = None
        self.labels_: Optional[np.ndarray] = None
        self._class_w: Optional[jnp.ndarray] = None   # (K,) 1/n_k
        self._Wv: Optional[jnp.ndarray] = None        # Wv_, once read
        self.last_info_: dict = {}
        self.plan_stats = {"built": 0, "hits": 0,
                           "disk_hits": 0, "disk_stores": 0}

    def _bump_plan_stat(self, key: str) -> None:
        """plan_stats increment, mirrored into the process registry
        (`repro_encoder_plan_cache_total{event=...}`) so every
        Embedder's cache behavior lands in one observable series."""
        self.plan_stats[key] += 1
        obs.counter("repro_encoder_plan_cache_total",
                    event={"hits": "tier1_hit", "built": "built",
                           "disk_hits": "disk_hit",
                           "disk_stores": "disk_store"}[key])

    # -- planning ----------------------------------------------------------

    def _resolve_backend(self, graph: Graph) -> Backend:
        if self._backend_spec == "auto":
            name = resolve_auto(graph.n, graph.s, mesh=self.mesh)
            if self.backend is None or self.backend.name != name:
                self.backend = get_backend(name)
        return self.backend

    def plan(self, graph) -> Plan:
        """Build (or reuse) the label-free preprocessing for `graph`
        (a Graph or a GraphSource).

        Tier 1 hits are O(1): the plan matches iff it was built against
        the very same edge arrays — a changed multiset means new arrays
        and a rebuild, same arrays (refinement rounds, serving rebuilds
        off a quiet store, benchmark repeats) skip all host packing.

        Tier 2 is content-addressed and survives the process: on a tier
        1 miss, the graph's fingerprint + resolved backend + config key
        a persistent entry holding the plan's host half — a hit skips
        `plan_host` (packing, Laplacian degrees)
        and only re-runs device placement.  Stale or corrupt entries
        fall back to a full rebuild; `plan_cache=None` disables the
        tier (or set REPRO_PLAN_CACHE=off process-wide)."""
        graph = as_graph(graph)
        backend = self._resolve_backend(graph)
        rp = self.config.row_partition
        if rp is not None:
            if not backend.supports_row_partition:
                from repro.encoder.backends import partition_backends
                raise ValueError(
                    f"backend {backend.name!r} has no owned-rows "
                    "accumulate path (row_partition) — only the "
                    "distributed backend lacks one (it shards "
                    "internally across the device mesh instead); "
                    "use one of the partition-aware backends: "
                    f"{', '.join(partition_backends())}")
            if rp[1] > graph.n:
                raise ValueError(
                    f"row_partition {rp} exceeds graph n={graph.n}")
        if self._plan is not None and self._plan.matches(
                graph, backend.name, self.config):
            self._bump_plan_stat("hits")
            return self._plan
        graph.validate()
        if self.Z_ is not None:
            # the fitted state belonged to the OLD plan's graph; keeping
            # it would let refit()/transform() serve stale or mismatched
            # results against the new plan
            self.Z_ = self.labels_ = self._class_w = self._Wv = None
            self._Yj = self._Yfit = None
            self._deltas_applied = 0
            self.last_info_ = {}
        with obs.span("encoder.plan", backend=backend.name,
                      n=graph.n, s=graph.s) as sp:
            meta = host = None
            cache = self.plan_cache if backend.persistable else None
            if cache is not None:
                meta = cache.describe(graph.fingerprint(), backend,
                                      self.config)
                host = cache.load(meta)
            if host is not None:
                self._bump_plan_stat("disk_hits")
                self._plan = backend.plan(graph, self.config,
                                          mesh=self.mesh, host=host)
                source = "disk"
            else:
                self._plan = backend.plan(graph, self.config,
                                          mesh=self.mesh)
                self._bump_plan_stat("built")
                if meta is not None and cache.store(meta,
                                                    self._plan.host):
                    self._bump_plan_stat("disk_stores")
                source = "built"
            sp.set(source=source)
        if obs.enabled():
            obs.observe("repro_encoder_plan_seconds", sp.duration,
                        backend=backend.name, source=source)
        return self._plan

    # -- fitting -----------------------------------------------------------

    def fit(self, graph, Y) -> "Embedder":
        """Embed `graph` (a Graph or GraphSource) under labels `Y`
        (int, -1 = unknown)."""
        plan = self.plan(graph)
        return self._embed(plan, Y)

    def refit(self, Y=None) -> "Embedder":
        """Re-embed under new labels, reusing the cached plan (no host
        packing).  Y=None re-runs with the current labels.

        Refuses to run after `partial_fit`: the cached plan holds the
        ORIGINAL edge multiset, so a refit would silently drop every
        applied delta — fit() on the live graph instead (serving does
        exactly that on rebuild)."""
        if self._plan is None or self.Z_ is None:
            raise NotFittedError(
                "refit() requires a fitted state for the cached plan "
                "(fit() first; a plan() on a different graph clears it)")
        self._check_no_pending_deltas("refit")
        self._bump_plan_stat("hits")
        return self._embed(self._plan, self.labels_ if Y is None else Y)

    def _check_no_pending_deltas(self, what: str) -> None:
        if self._deltas_applied:
            raise RuntimeError(
                f"{what}() after {self._deltas_applied} partial_fit(s) "
                "would re-embed the plan's ORIGINAL edge multiset and "
                "silently discard the applied deltas; fit() on the "
                "live graph instead")

    def _embed(self, plan: Plan, Y) -> "Embedder":
        with obs.span("encoder.fit", backend=self.backend.name,
                      n=plan.n, s=plan.s) as sp:
            Y = np.asarray(Y, np.int32)
            if Y.shape != (plan.n,):
                raise ValueError(f"Y shape {Y.shape} != ({plan.n},)")
            if Y.size and Y.max() >= self.config.K:
                raise ValueError(f"label {Y.max()} >= K={self.config.K}")
            self.labels_ = Y.copy()
            self._project(plan, jnp.asarray(Y))
            self._Yfit = self._Yj   # supervised set: pinned by refine()
            self._count_work(plan, Y)   # host work: overlaps the device
            sp.fence(self.Z_)       # bill the async scatter to the fit
        if obs.enabled():       # a fit that raised is not timed
            obs.observe("repro_encoder_fit_seconds", sp.duration,
                        backend=self.backend.name)
            if plan.s and sp.duration > 0:
                obs.gauge("repro_encoder_fit_edges_per_s",
                          plan.s / sp.duration, backend=self.backend.name)
        self._deltas_applied = 0
        return self

    def _project(self, plan: Plan, Yj: jnp.ndarray) -> None:
        """Embed under device labels Yj and keep it as the fitted
        state; `Wv_` is built from the class weights when first read."""
        self._Yj, self._Wv = Yj, None
        self._class_w = class_weights(Yj, self.config.K)
        self.Z_, self.last_info_ = self.backend.embed(plan, Yj,
                                                      self._class_w)

    @property
    def Wv_(self) -> Optional[jnp.ndarray]:
        if self._Wv is None and self._class_w is not None:
            self._Wv = make_w(self._Yj, self.config.K, self._class_w)
        return self._Wv

    def _count_work(self, plan: Plan, Y: Optional[np.ndarray]) -> None:
        """Count one embed's work from the plan's static shapes and the
        host labels (obs-on only; nothing is read from the device):
        the slots the device runs over, padding included, and the real
        contributions split by whether their donor's label is known.
        Y=None: every node carries a label (refinement rounds)."""
        if not obs.enabled():
            return
        slots = self.backend.slots(plan)
        if slots is None:
            return
        name, donors = self.backend.name, plan.donor_counts()
        total = int(donors.sum())
        labeled = total if Y is None else int(donors @ (Y >= 0))
        obs.counter("repro_kernel_slots_total", slots, backend=name)
        obs.counter("repro_kernel_contributions_total", labeled,
                    backend=name, donor="labeled")
        obs.counter("repro_kernel_contributions_total", total - labeled,
                    backend=name, donor="unlabeled")

    def partial_fit(self, delta: Graph, *, sign: float = 1.0
                    ) -> "Embedder":
        """Fold an edge delta into Z exactly (GEE is linear in the edge
        multiset).  sign=+1 inserts, sign=-1 deletes.  Uses the OWNED
        (labels_, Wv_) pair, so the Wv-mismatch footgun of calling
        `gee_apply_delta` directly cannot occur.  Batches are padded to
        power-of-two buckets: one jit compile per bucket size."""
        if self.Z_ is None:
            raise NotFittedError("partial_fit() before fit()")
        if self.config.laplacian:
            raise ValueError(
                "partial_fit is exact only for laplacian=False: degree "
                "scaling makes Z nonlinear in the edge multiset — refit "
                "on the updated graph instead")
        if delta.n != self.n_:
            raise ValueError(f"delta graph has n={delta.n}, fitted "
                             f"n={self.n_}")
        delta.validate()
        if delta.s == 0:
            return self
        t0 = obs.tick()
        rp = self.config.row_partition
        if rp is not None:
            # owned-rows path: bucket the delta by owned destination on
            # the host (O(batch)), scatter into the (n_local, K) slice.
            # Contributions landing outside [lo, hi) never touch owned
            # rows (laplacian is rejected above, so Z is linear and
            # non-incident edges are exact no-ops here).
            rows, src, w = owned_contributions(delta, delta.w, *rp)
            if rows.shape[0] == 0:
                return self
            pad = bucket_size(rows.shape[0]) - rows.shape[0]
            if pad:
                rows = np.concatenate([rows, np.zeros(pad, np.int32)])
                src = np.concatenate([src, np.zeros(pad, np.int32)])
                w = np.concatenate([w, np.zeros(pad, np.float32)])
            self.Z_ = gee_apply_delta_owned(
                self.Z_, jnp.asarray(rows), jnp.asarray(src),
                jnp.asarray(w), self._Yj, self.Wv_, K=self.config.K,
                sign=sign)
            self._deltas_applied += 1
            self._record_partial_fit(t0, delta.s)
            return self
        padded = delta.pad_to(bucket_size(delta.s))
        self.Z_ = gee_apply_delta(
            self.Z_, jnp.asarray(padded.u), jnp.asarray(padded.v),
            jnp.asarray(padded.w), self._Yj, self.Wv_,
            K=self.config.K, sign=sign)
        self._deltas_applied += 1
        self._record_partial_fit(t0, delta.s)
        return self

    def partial_fit_norm(self, delta: Graph, *, sign: float = 1.0
                         ) -> jnp.ndarray:
        """`partial_fit` fused with renormalization: fold the delta
        into Z AND produce the row-normalized slice in one pallas pass
        (`kernels.query_fused.gee_delta_renorm`) — the serving
        partial_fit-then-query turnaround, where the normalized rows
        are needed immediately and a separate normalize pass would
        re-read all of Z from HBM.  Same exactness contract as
        `partial_fit` (linear updates only); classes/values resolve on
        the host from the fitted (labels_, Wv_) pair and pack by
        destination tile like the fit-path kernel.  Returns Zn — the
        unit-normalized fitted rows (the shard's query cache)."""
        if self.Z_ is None:
            raise NotFittedError("partial_fit_norm() before fit()")
        if self.config.laplacian:
            raise ValueError(
                "partial_fit_norm is exact only for laplacian=False: "
                "degree scaling makes Z nonlinear in the edge multiset "
                "— refit on the updated graph instead")
        if delta.n != self.n_:
            raise ValueError(f"delta graph has n={delta.n}, fitted "
                             f"n={self.n_}")
        delta.validate()
        from repro.kernels.ops import pack_edges
        from repro.kernels.query_fused import gee_delta_renorm
        t0 = obs.tick()
        rp = self.config.row_partition
        if delta.s == 0:
            rows = src = np.zeros(0, np.int32)
            w = np.zeros(0, np.float32)
        elif rp is not None:
            rows, src, w = owned_contributions(delta, delta.w, *rp)
        else:
            u, v = np.asarray(delta.u), np.asarray(delta.v)
            rows = np.concatenate([u, v]).astype(np.int32)
            src = np.concatenate([v, u]).astype(np.int32)
            w = np.concatenate([delta.w, delta.w]).astype(np.float32)
        Ys = self.labels_[src]
        clsv = np.maximum(Ys, 0).astype(np.int32)
        Wvh = np.asarray(self.Wv_)
        val = np.where(Ys >= 0, Wvh[src] * w,
                       np.float32(0)) * np.float32(sign)
        n_local = int(self.Z_.shape[0])
        rb, cb, vb, _ = pack_edges(rows, clsv, val.astype(np.float32),
                                   n_local, self.config.tile_n,
                                   self.config.edge_block)
        self.Z_, Zn = gee_delta_renorm(
            self.Z_, rb, cb, vb, tile_n=self.config.tile_n,
            interpret=self.config.interpret)
        if rows.shape[0]:
            self._deltas_applied += 1
        self._record_partial_fit(t0, delta.s)
        return Zn

    def _record_partial_fit(self, t0: float, s: int) -> None:
        """Registry metrics for one applied delta (obs-on only: the
        fence synchronizes device work so the latency is real)."""
        if not obs.enabled():
            return
        jax.block_until_ready(self.Z_)
        obs.observe("repro_encoder_partial_fit_seconds", obs.tock(t0),
                    backend=self.backend.name)
        obs.counter("repro_encoder_delta_edges_total", s)

    # -- refinement --------------------------------------------------------

    def refine(self, key=None) -> "Embedder":
        """Unsupervised GEE clustering (embed -> k-means -> reassign,
        `config.refine_iters` rounds).  Known labels in `labels_` stay
        pinned; unknowns bootstrap randomly.  Updates Z_ and labels_.

        Each round's embed dispatches through the CONFIGURED backend
        against the cached plan (labels are the only thing that changes
        round to round — exactly the plan/embed split), so refinement
        keeps the backend's memory/placement properties instead of
        falling back to a single-device full-graph pass."""
        if self._plan is None or self._Yfit is None:
            raise NotFittedError("refine() before fit()")
        self._require_full_rows("refine")
        self._check_no_pending_deltas("refine")
        key = jax.random.PRNGKey(0) if key is None else key
        cfg = self.config
        with obs.span("encoder.refine",
                      metric="repro_encoder_refine_seconds",
                      backend=self.backend.name,
                      iters=cfg.refine_iters) as sp:
            # pin only the labels SUPERVISED at fit time — not a
            # previous refine()'s assignments, so repeated refines
            # re-bootstrap the unknowns instead of freezing on round
            # one's clustering
            Y0 = self._Yfit
            rand = jax.random.randint(key, (self._plan.n,), 0, cfg.K,
                                      jnp.int32)
            labels = jnp.where(Y0 >= 0, Y0, rand)
            for _ in range(cfg.refine_iters):
                Z, _ = self.backend.embed(self._plan, labels,
                                          class_weights(labels, cfg.K))
                self._count_work(self._plan, None)
                labels = _kmeans_reassign(Z, labels, Y0, K=cfg.K,
                                          kmeans_iters=cfg.kmeans_iters)
            self.labels_ = np.asarray(labels)
            self._project(self._plan, labels)
            self._count_work(self._plan, None)
            sp.fence(self.Z_)
        return self

    # -- queries -----------------------------------------------------------

    @property
    def n_(self) -> int:
        if self._plan is None:
            raise NotFittedError("not fitted")
        return self._plan.n

    def _require_full_rows(self, what: str) -> None:
        if self.config.row_partition is not None:
            raise RuntimeError(
                f"{what}() needs the full embedding, but this Embedder "
                f"owns only rows {self.config.row_partition} "
                "(row_partition) — run it on an unpartitioned Embedder")

    def _rows(self, nodes):
        """Z rows for `nodes` (GLOBAL ids, also under a row partition),
        bounds-checked (jnp gather would silently CLAMP out-of-range
        ids — a stale or unowned node id must raise, not return a
        plausible wrong row)."""
        if self.Z_ is None:
            raise NotFittedError("not fitted")
        if nodes is None:
            return self.Z_
        nodes = np.asarray(nodes)
        lo, hi = self.config.row_partition or (0, self.n_)
        if nodes.size and (nodes.min() < lo or nodes.max() >= hi):
            owned = " owned" if self.config.row_partition else ""
            raise IndexError(f"node ids must be in{owned} [{lo}, {hi}), "
                             f"got range [{nodes.min()}, {nodes.max()}]")
        return self.Z_[jnp.asarray(nodes - lo)]

    def transform(self, nodes=None) -> np.ndarray:
        """Z rows for `nodes` (all fitted rows if None — the owned
        block under a row partition), in config.dtype.  Node ids are
        always GLOBAL."""
        t0 = obs.tick()
        Z = self._rows(nodes)
        out = np.asarray(Z.astype(jnp.dtype(self.config.dtype)))
        if obs.enabled():
            obs.observe("repro_encoder_transform_seconds",
                        obs.tock(t0))
        return out

    def predict(self, nodes=None) -> np.ndarray:
        """argmax-Z class prediction for `nodes` (all fitted nodes if
        None; global ids)."""
        Z = self._rows(nodes)
        return np.asarray(jnp.argmax(Z, axis=1).astype(jnp.int32))

    def to_features(self, d_model: int, *, key=None,
                    blend: float = 0.5) -> np.ndarray:
        """Project the fitted Z into an (n, d_model) feature table —
        the GEE -> LM bridge (embedding-table initialization).

        Rows of Z are unit-normalized, rotated K -> d_model with a
        fixed random near-isometry, and blended with scaled Gaussian
        noise; the result matches a standard 1/sqrt(d) init in scale
        but starts topic-structured (nodes GEE places together get
        similar feature rows).  ``blend`` in [0, 1]: 1 = pure
        structure, 0 = pure noise."""
        if self.Z_ is None:
            raise NotFittedError("to_features() before fit()")
        self._require_full_rows("to_features")
        key = jax.random.PRNGKey(0) if key is None else key
        k_rot, k_noise = jax.random.split(key)
        Z = self.Z_ / jnp.maximum(
            jnp.linalg.norm(self.Z_, axis=1, keepdims=True), 1e-9)
        K = self.config.K
        R = jax.random.normal(k_rot, (K, d_model),
                              jnp.float32) / np.sqrt(K)
        base = Z @ R
        noise = jax.random.normal(k_noise, (self.n_, d_model),
                                  jnp.float32)
        scale = 1.0 / np.sqrt(d_model)
        table = scale * (blend * base * np.sqrt(d_model)
                         + (1 - blend) * noise)
        return np.asarray(table, np.float32)
