"""Backend registry: every execution strategy behind one interface.

A backend turns a `Plan` plus the *current* labels into Z.  All of them
compute the same mathematical object (conformance-tested); they differ
in where the scatter runs and how contributions move:

  numpy           `ref_python.gee_numpy` — the compiled-serial oracle.
  xla             `core.gee` — jitted XLA scatter-add (CPU/GPU/TPU).
  pallas          `kernels.gee_scatter` — destination-tiled one-hot
                  matmul; edges packed ONCE at plan time by destination
                  tile with their *source node* (not class), so label
                  changes re-resolve on device and never re-pack.
  streaming       chunked accumulate (O(chunk) device memory) —
                  the out-of-core / serving-rebuild path.
  distributed:M   `core.distributed.gee_sharded` for M in
                  {replicated, reduce_scatter, a2a, ring} — SPMD
                  collectives; the plan pads edges/rows to the mesh and
                  measures the exact zero-drop capacity factor once.

Register new strategies with ``@register_backend("name")``; callers
select them by name through ``Embedder(..., backend="name")`` without
touching any call site.  ``backend="auto"`` (the `EncoderConfig`
default) picks a strategy at plan time from (n, s, device kind, device
count) via the overridable `AUTO_POLICY` table below.

Every backend's plan is built in two halves:

  plan_host      expensive, label-free, DEVICE-FREE artifacts (numpy
                 arrays / scalars) — persistable by the cross-process
                 plan cache (`repro.encoder.plan_cache`);
  plan_finalize  cheap per-process work: device uploads, mesh
                 placement, chunk views — always re-run.

A cache hit hands plan() the stored host dict and skips plan_host
entirely; that is the whole point of the persistent tier.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple, Type

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.encoder.config import EncoderConfig
from repro.encoder.plan import Plan, effective_weights, owned_contributions
from repro.graph.edges import Graph

_REGISTRY: Dict[str, Type["Backend"]] = {}


def register_backend(name: str):
    """Class decorator: make a Backend constructible by name."""
    def deco(cls: Type["Backend"]) -> Type["Backend"]:
        cls.name = name
        _REGISTRY[name] = cls
        return cls
    return deco


def get_backend(name: str) -> "Backend":
    try:
        return _REGISTRY[name]()
    except KeyError:
        raise KeyError(f"unknown backend {name!r}; registered: "
                       f"{', '.join(sorted(_REGISTRY))}") from None


def list_backends() -> list[str]:
    return sorted(_REGISTRY)


def partition_backends() -> list[str]:
    """Registered backends implementing the owned-rows accumulate path
    (`EncoderConfig.row_partition`) — the suggestion list for the
    plan-time rejection of a partition-unaware backend."""
    return sorted(n for n, c in _REGISTRY.items()
                  if c.supports_row_partition)


class Backend:
    """One execution strategy: label-free `plan`, label-dependent `embed`."""

    name: str = "?"
    #: scatter-path backends reproduce the oracle to float tolerance;
    #: bucketed collective modes additionally depend on capacity padding.
    exact: bool = True
    #: bump when the plan_host artifact layout changes — stale disk
    #: entries from older code then read as misses, not wrong plans
    plan_version: int = 1
    #: whether plan_host output may be persisted cross-process
    persistable: bool = True
    #: whether this backend implements the owned-rows accumulate path
    #: (`EncoderConfig.row_partition`): an (n_local, K) accumulator over
    #: contributions pre-bucketed by owned destination
    supports_row_partition: bool = False

    def cache_context(self, *, mesh=None) -> str:
        """Runtime context baked into the persistent-cache key (e.g.
        device count, which distributed capacity factors depend on)."""
        return ""

    def plan_host(self, graph: Graph, config: EncoderConfig,
                  w_eff: np.ndarray, *, mesh=None) -> Dict:
        """Backend-specific expensive host artifacts (numpy arrays /
        scalars only; "w_eff" is added by `plan`)."""
        return {}

    def plan_finalize(self, plan: Plan, graph: Graph, *,
                      mesh=None) -> None:
        """Populate plan.data from (graph, plan.host): device uploads,
        mesh placement, chunk views — cheap, re-run every process."""
        raise NotImplementedError

    def plan(self, graph: Graph, config: EncoderConfig, *, mesh=None,
             host: Optional[Dict] = None) -> Plan:
        """Build the plan; `host` (from the persistent cache) skips the
        expensive half.

        w_eff only rides the host dict (and hence disk) when Laplacian
        scaling makes it a real O(s) artifact; unscaled it IS graph.w,
        so persisting it would bloat every cache entry with a full
        per-edge copy that costs more to load than to recompute.
        (Partitioned plans fold w_eff into the owned contribution
        arrays, so they never persist the full-length copy either.)"""
        built = host is None
        if built:
            w_eff = effective_weights(graph, config)
            keep_w = config.laplacian and config.row_partition is None
            host = {**({"w_eff": w_eff} if keep_w else {}),
                    **self.plan_host(graph, config, w_eff, mesh=mesh)}
        if config.row_partition is not None:
            # owned plans folded the scaling into o_w: don't retain (or,
            # on a cache hit, rebuild) a second full-length copy that no
            # partitioned finalize/embed path ever reads
            w_eff = graph.w
        elif not built:
            w_eff = (host["w_eff"] if "w_eff" in host
                     else effective_weights(graph, config))
        p = Plan(backend=self.name, config=config, n=graph.n, s=graph.s,
                 w_eff=np.asarray(w_eff, np.float32), host=host,
                 **Plan.anchors(graph))
        self.plan_finalize(p, graph, mesh=mesh)
        return p

    def embed(self, plan: Plan, Yj: jnp.ndarray, Wv: jnp.ndarray
              ) -> Tuple[jnp.ndarray, dict]:
        """Return (Z (n, K) float32, info dict)."""
        raise NotImplementedError

    def slots(self, plan: Plan) -> Optional[int]:
        """Contribution slots the device processes per embed, padding
        included (`repro_kernel_slots_total`), from the plan's static
        shapes; None where the backend does not count its work.  The
        default is one slot per contribution: nothing padded."""
        return int(plan.donor_counts().sum())


def _owned_plan_host(graph: Graph, config: EncoderConfig,
                     w_eff: np.ndarray) -> Dict:
    """Shared host half of a partitioned plan: contributions bucketed
    by owned destination, destination rows remapped to [0, n_local)."""
    rows, src, w = owned_contributions(graph, w_eff,
                                       *config.row_partition)
    return {"o_rows": rows, "o_src": src, "o_w": w}


@register_backend("numpy")
class NumpyBackend(Backend):
    """`ref_python.gee_numpy`: the host-side oracle every other backend
    is conformance-checked against."""

    supports_row_partition = True

    def plan_host(self, graph, config, w_eff, *, mesh=None):
        if config.row_partition is None:
            return {}
        return _owned_plan_host(graph, config, w_eff)

    def plan_finalize(self, p, graph, *, mesh=None):
        if p.config.row_partition is None:
            p.data = {"u": np.asarray(graph.u), "v": np.asarray(graph.v)}
        else:
            h = p.host
            p.data = {"rows": np.asarray(h["o_rows"], np.int32),
                      "src": np.asarray(h["o_src"], np.int32),
                      "w": np.asarray(h["o_w"], np.float32)}

    def embed(self, plan, Yj, Wv):
        from repro.core.ref_python import gee_numpy, gee_numpy_owned
        Y = np.asarray(Yj)
        d = plan.data
        if plan.config.row_partition is not None:
            Z = gee_numpy_owned(d["rows"], d["src"], d["w"], Y,
                                np.asarray(Wv), plan.config.K,
                                plan.n_local)
            return jnp.asarray(Z), {}
        Z = gee_numpy(d["u"], d["v"], plan.w_eff, Y,
                      plan.config.K, plan.n)
        return jnp.asarray(Z), {}


@register_backend("xla")
class XlaBackend(Backend):
    """`core.gee` (jitted XLA scatter-add) — the single-device hot
    path.  Passes the Embedder-owned Wv through `gee`'s precompute
    parameter instead of re-deriving it from Y.  Under a row partition
    it scatters the pre-bucketed owned contributions into an
    (n_local, K) accumulator (`core.gee.gee_owned`)."""

    supports_row_partition = True

    def plan_host(self, graph, config, w_eff, *, mesh=None):
        if config.row_partition is None:
            return {}
        return _owned_plan_host(graph, config, w_eff)

    def plan_finalize(self, p, graph, *, mesh=None):
        if p.config.row_partition is None:
            p.data = {"u": jnp.asarray(graph.u),
                      "v": jnp.asarray(graph.v),
                      "w": jnp.asarray(p.w_eff)}
        else:
            h = p.host
            p.data = {"rows": jnp.asarray(np.asarray(h["o_rows"],
                                                     np.int32)),
                      "src": jnp.asarray(np.asarray(h["o_src"],
                                                    np.int32)),
                      "w": jnp.asarray(np.asarray(h["o_w"],
                                                  np.float32))}

    def embed(self, plan, Yj, Wv):
        from repro.core.gee import gee, gee_owned
        d = plan.data
        if plan.config.row_partition is not None:
            Z = gee_owned(d["rows"], d["src"], d["w"], Yj, Wv,
                          K=plan.config.K, n_local=plan.n_local)
            return Z, {}
        Z = gee(d["u"], d["v"], d["w"], Yj, K=plan.config.K, n=plan.n,
                Wv=Wv)
        return Z, {}


@register_backend("pallas")
class PallasBackend(Backend):
    """Destination-tiled one-hot matmul kernel.

    The plan packs (tile-local row, source node, weight) — all
    label-free — so refits resolve classes/values on device from the
    current (Y, Wv) and skip the O(s log s) host sort entirely.  Padded
    slots carry w = 0 and are no-ops for any labeling.  The packed
    buffers are the host half: a persistent-cache hit skips the sort in
    a fresh process too.

    Under a row partition the contributions bucketed by owned
    destination (`plan.owned_contributions`, destinations remapped to
    [0, hi - lo)) feed the SAME destination packing over the local row
    range, so sharded rebuilds get both the edge-parallel kernel and
    the O(n/p) (hi - lo, K) accumulator; the packed blocks are the
    persisted tier-2 artifact, keyed on the partition via the config
    token like every other backend.

    The kernel's compile/interpret mode resolves per platform at plan
    finalize (`kernels.resolve_interpret`: compiled on TPU/GPU,
    interpreter elsewhere unless the config forces a bool); the
    resolved mode lands in plan.data, the embed info dict, and the
    ``repro_kernels_pallas_interpret_mode`` gauge — it is per-process
    runtime state, never persisted.
    """

    supports_row_partition = True
    #: v2: partitioned plans pack over local rows [0, hi - lo)
    #: v3: packed blocks are (T, BPT, 1, EB), the layout Mosaic tiles
    plan_version = 3

    def plan_host(self, graph, config, w_eff, *, mesh=None):
        from repro.kernels.ops import _round_up, pack_edges
        if config.row_partition is not None:
            lo, hi = config.row_partition
            dst, src, w2 = owned_contributions(graph, w_eff, lo, hi)
            n_rows = hi - lo
        else:
            u, v = np.asarray(graph.u), np.asarray(graph.v)
            dst = np.concatenate([u, v])
            src = np.concatenate([v, u])          # label donor
            w2 = np.concatenate([w_eff, w_eff])
            n_rows = graph.n
        rows, srcb, wb, T = pack_edges(dst, src, w2, n_rows,
                                       config.tile_n, config.edge_block)
        return {"rows": rows, "src": srcb, "w_packed": wb, "T": T,
                "kdim": _round_up(config.K, 8)}

    def plan_finalize(self, p, graph, *, mesh=None):
        from repro.kernels.gee_scatter import (interpret_mode_name,
                                               resolve_interpret)
        h = p.host
        interp = resolve_interpret(p.config.interpret)
        p.data = {"rows": jnp.asarray(h["rows"]),
                  "src": jnp.asarray(h["src"]),
                  "w": jnp.asarray(np.asarray(h["w_packed"], np.float32)),
                  "T": int(h["T"]), "kdim": int(h["kdim"]),
                  "interpret": interp}
        if obs.enabled():
            obs.gauge("repro_kernels_pallas_interpret_mode",
                      1.0 if interp else 0.0,
                      mode=interpret_mode_name(interp))

    def embed(self, plan, Yj, Wv):
        from repro.kernels.gee_scatter import gee_scatter_pallas
        d, cfg = plan.data, plan.config
        with obs.span("encoder.gather"):
            Ys = Yj[d["src"]]
            cls = jnp.maximum(Ys, 0)
            val = jnp.where(Ys >= 0, Wv[d["src"]] * d["w"], 0.0)
        with obs.span("encoder.scatter"):
            Z = gee_scatter_pallas(d["rows"], cls, val, num_tiles=d["T"],
                                   tile_n=cfg.tile_n, kdim=d["kdim"],
                                   interpret=d["interpret"])
            Z = Z[:plan.n_local, :cfg.K]
        return Z, {"interpret": d["interpret"]}

    def slots(self, plan):
        return int(plan.data["rows"].size)         # T * bpt * EB


@register_backend("streaming")
class StreamingBackend(Backend):
    """`gee_streaming`'s accumulate loop over bucket-padded chunks, with
    the Embedder-owned Wv: bounded DEVICE working set — each chunk is
    uploaded, folded into Z, and released, so only O(chunk) edge data
    plus Z ever lives on device (the serving-rebuild and out-of-core
    ingestion path).  Chunks stay host-side in the plan (non-tail
    chunks are views of the caller's arrays, not copies; chunking is
    cheap, so only w_eff rides the persistent cache).

    Under a row partition the chunks are owned-destination
    contribution triples and the accumulator is (n_local, K) — device
    memory is O(chunk + n/p), the sharded serving rebuild path."""

    supports_row_partition = True

    def plan_host(self, graph, config, w_eff, *, mesh=None):
        if config.row_partition is None:
            return {}
        # the O(s) destination bucketing is the expensive half here —
        # persist it; chunking the bucketed arrays stays per-process
        return _owned_plan_host(graph, config, w_eff)

    def plan_finalize(self, p, graph, *, mesh=None):
        from repro.graph.edges import chunk_edges
        if p.config.row_partition is None:
            p.data = {"chunks": list(chunk_edges(
                np.asarray(graph.u, np.int32),
                np.asarray(graph.v, np.int32),
                p.w_eff, p.config.chunk_size))}
        else:
            h = p.host
            # chunk_edges pads tails with (0, 0, 0.0) triples — local
            # row 0 with w = 0 is a no-op contribution for any labeling
            p.data = {"chunks": list(chunk_edges(
                np.asarray(h["o_rows"], np.int32),
                np.asarray(h["o_src"], np.int32),
                np.asarray(h["o_w"], np.float32),
                p.config.chunk_size))}

    def embed(self, plan, Yj, Wv):
        from repro.core.gee import gee_streaming, gee_streaming_owned
        cfg = plan.config
        if cfg.row_partition is not None:
            Z = gee_streaming_owned(
                ((jnp.asarray(r), jnp.asarray(s), jnp.asarray(w))
                 for (r, s, w) in plan.data["chunks"]),
                Yj, K=cfg.K, n_local=plan.n_local, Wv=Wv)
        else:
            Z = gee_streaming(
                ((jnp.asarray(u), jnp.asarray(v), jnp.asarray(w))
                 for (u, v, w) in plan.data["chunks"]),
                Yj, K=cfg.K, n=plan.n, Wv=Wv)
        return Z, {"chunks": len(plan.data["chunks"])}

    def slots(self, plan):
        rows = sum(c[0].shape[0] for c in plan.data["chunks"])
        # unpartitioned chunk rows are edges: two contributions each
        return rows if plan.config.row_partition is not None else 2 * rows


class DistributedBackend(Backend):
    """SPMD collectives over the edge mesh (`core.distributed`).

    The plan pads edges and rows to the mesh, places the padded arrays,
    and — for bucketed modes — measures the exact zero-drop capacity
    factor from the owner histogram (an O(s) host pass now done once
    instead of per fit).  The capacity factor depends on the device
    count, so it is the persisted host artifact and the device count is
    baked into the cache key (`cache_context`); padding and placement
    are per-process finalize work.
    """

    mode = "ring"
    exact = False          # bucketed modes depend on capacity padding

    @staticmethod
    def _mesh(mesh):
        from repro.core.distributed import edge_mesh
        return mesh if mesh is not None else edge_mesh()

    def cache_context(self, *, mesh=None) -> str:
        return f"nd={self._mesh(mesh).devices.size}"

    def plan_host(self, graph, config, w_eff, *, mesh=None):
        from repro.core.distributed import exact_capacity_factor
        nd = self._mesh(mesh).devices.size
        cf = config.capacity_factor
        if cf is None and self.mode in ("a2a", "ring"):
            cf = exact_capacity_factor(graph, nd)
        return {"capacity_factor": cf if cf is not None else 2.0}

    def plan_finalize(self, p, graph, *, mesh=None):
        from repro.core.distributed import pad_rows
        mesh = self._mesh(mesh)
        nd = mesh.devices.size
        n_pad = pad_rows(graph.n, nd)
        s_pad = pad_rows(graph.s, nd)
        g = Graph(np.asarray(graph.u), np.asarray(graph.v), p.w_eff,
                  graph.n).pad_to(s_pad)
        p.data = {"mesh": mesh, "n_pad": n_pad,
                  "capacity_factor": float(p.host["capacity_factor"]),
                  "u": jnp.asarray(g.u), "v": jnp.asarray(g.v),
                  "w": jnp.asarray(g.w)}

    def slots(self, plan):
        return None            # capacity padding differs by mode

    def embed(self, plan, Yj, Wv):
        from repro.core.distributed import gee_sharded
        d, cfg = plan.data, plan.config
        Y_pad = jnp.concatenate([
            Yj, jnp.full(d["n_pad"] - plan.n, -1, jnp.int32)])
        Z, dropped = gee_sharded(
            d["u"], d["v"], d["w"], Y_pad, K=cfg.K, n=d["n_pad"],
            mesh=d["mesh"], mode=self.mode,
            capacity_factor=d["capacity_factor"])
        return Z[:plan.n], {"dropped": int(dropped)}


for _mode in ("replicated", "reduce_scatter", "a2a", "ring"):
    # replicated / reduce_scatter are pure scatter+collective paths
    # (float-exact); a2a / ring bucket with capacity padding.
    register_backend(f"distributed:{_mode}")(
        type(f"Distributed{_mode.title().replace('_', '')}Backend",
             (DistributedBackend,),
             {"mode": _mode,
              "exact": _mode in ("replicated", "reduce_scatter")}))


# -- backend="auto": the plan-time selection policy -------------------------

#: edge count past which a single device should stop holding the whole
#: edge list and stream chunks instead (tunable; ~3 int/float arrays of
#: this length is the resident cost the threshold bounds)
AUTO_STREAMING_EDGES = 32_000_000


def _rule_multi_device(n, s, device_kind, device_count):
    return "distributed:reduce_scatter" if device_count > 1 else None


def _rule_out_of_core(n, s, device_kind, device_count):
    return "streaming" if s >= AUTO_STREAMING_EDGES else None


def _rule_tpu_kernel(n, s, device_kind, device_count):
    return "pallas" if device_kind == "tpu" else None


#: ordered (name, rule(n, s, device_kind, device_count) -> backend name
#: or None) pairs; the first rule returning a name wins, fallback is
#: "xla".  Overridable: mutate this list (insert/replace rules) to
#: change policy globally — it is data, not code.
AUTO_POLICY: List[Tuple[str, Callable]] = [
    ("multi_device", _rule_multi_device),
    ("out_of_core", _rule_out_of_core),
    ("tpu_kernel", _rule_tpu_kernel),
]


def resolve_auto(n: int, s: int, *, device_kind: Optional[str] = None,
                 device_count: Optional[int] = None, mesh=None) -> str:
    """Resolve `backend="auto"` for a graph of (n, s) on this runtime.

    Device kind/count default to the provided mesh, else
    `jax.devices()`.  Walks `AUTO_POLICY` in order; first hit wins,
    fallback "xla".  Pure given explicit kind/count (unit-testable
    without hardware)."""
    if device_kind is None or device_count is None:
        devs = (list(mesh.devices.flat) if mesh is not None
                else jax.devices())
        if device_kind is None:
            device_kind = devs[0].platform
        if device_count is None:
            device_count = len(devs)
    for _, rule in AUTO_POLICY:
        name = rule(n, s, device_kind, device_count)
        if name is not None:
            return name
    return "xla"
