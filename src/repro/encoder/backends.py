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
  distributed:reduce_scatter
                  `core.distributed` — SPMD over the device mesh; the
                  plan pads edges/rows to the mesh, places the edges
                  sharded over it and packs each chip's edges on the
                  chips for the pallas kernel once; each embed runs the
                  kernel on every chip and one reduce-scatter.

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

import functools
from typing import Callable, Dict, List, Optional, Tuple, Type

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.gee import make_w
from repro.encoder import class_fill
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
    #: bump when the plan_host artifact layout changes — stale disk
    #: entries from older code then read as misses, not wrong plans
    plan_version: int = 1
    #: whether plan_host output may be persisted cross-process
    persistable: bool = True
    #: whether this backend implements the owned-rows accumulate path
    #: (`EncoderConfig.row_partition`): an (n_local, K) accumulator over
    #: contributions pre-bucketed by owned destination
    supports_row_partition: bool = False

    def plan_host(self, graph: Graph, config: EncoderConfig,
                  w_eff: np.ndarray) -> Dict:
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
                    **self.plan_host(graph, config, w_eff)}
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

    def embed(self, plan: Plan, Yj: jnp.ndarray, class_w: jnp.ndarray
              ) -> Tuple[jnp.ndarray, dict]:
        """Return (Z (n, K) float32, info dict).

        class_w: the (K,) class weights 1/n_k of the labels Yj
        (`core.gee.class_weights`).  A contribution's weight depends
        only on its class, which is its column of Z: pallas and
        distributed:reduce_scatter scale Z's columns by class_w; the
        rest weight each contribution by the per-node
        `make_w(Yj, K, class_w)`."""
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

    def plan_host(self, graph, config, w_eff):
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

    def embed(self, plan, Yj, class_w):
        from repro.core.ref_python import gee_numpy, gee_numpy_owned
        Y = np.asarray(Yj)
        d = plan.data
        if plan.config.row_partition is not None:
            Wv = make_w(Yj, plan.config.K, class_w)
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
    path.  Builds Wv from the Embedder's class weights and passes it
    through `gee`'s precompute parameter.  Under a row partition
    it scatters the pre-bucketed owned contributions into an
    (n_local, K) accumulator (`core.gee.gee_owned`)."""

    supports_row_partition = True

    def plan_host(self, graph, config, w_eff):
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

    def embed(self, plan, Yj, class_w):
        from repro.core.gee import gee, gee_owned
        d = plan.data
        Wv = make_w(Yj, plan.config.K, class_w)
        if plan.config.row_partition is not None:
            Z = gee_owned(d["rows"], d["src"], d["w"], Yj, Wv,
                          K=plan.config.K, n_local=plan.n_local)
            return Z, {}
        Z = gee(d["u"], d["v"], d["w"], Yj, K=plan.config.K, n=plan.n,
                Wv=Wv)
        return Z, {}


@functools.partial(jax.jit, static_argnames=("n",))
def _scale_columns(Z, class_w, *, n: int):
    """The kernel's padded accumulator cut to n rows and K =
    class_w.size columns, column k scaled by class k's weight 1/n_k."""
    return Z[:n, :class_w.shape[0]] * class_w


@register_backend("pallas")
class PallasBackend(Backend):
    """Destination-tiled one-hot matmul kernel.

    The plan packs (tile-local row, source node, weight) — all
    label-free — so refits resolve each slot's class on device from the
    current Y and skip the O(s log s) host sort entirely.  The kernel
    scatters the packed weight under that class; an unlabeled donor's
    class -1 matches no column of the kernel's one-hot, so it adds
    nothing.  The classes come by one of two routes, chosen per embed
    from the host labels (`class_fill`): where labeled donors own few
    slots, their classes are written into a buffer of -1 through the
    plan's donor index (route "labeled"); else Y is gathered at every
    slot (route "gather", also taken while tracing).  The projection
    weight 1/n_k depends only on the class, which is the contribution's
    column of Z, so it is applied once per column of the (n_local, K)
    result (`class_w`), not per slot.  Padded slots
    carry w = 0 and donor n, which is no node, and are no-ops for any
    labeling.  The packed buffers are the host half: a persistent-cache
    hit skips the sort in a fresh process too; the donor index is built
    from them on the device in every process (`plan_finalize`).

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
    #: v4: padded slots hold donor n, so the donor index skips them
    plan_version = 4

    def plan_host(self, graph, config, w_eff):
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
                                       config.tile_n, config.edge_block,
                                       pad_cls=graph.n)
        return {"rows": rows, "src": srcb, "w_packed": wb, "T": T,
                "kdim": _round_up(config.K, 8)}

    def plan_finalize(self, p, graph, *, mesh=None):
        from repro.kernels.gee_scatter import (interpret_mode_name,
                                               resolve_interpret)
        h = p.host
        interp = resolve_interpret(p.config.interpret)
        src = jnp.asarray(h["src"])
        p.data = {"rows": jnp.asarray(h["rows"]), "src": src,
                  "w": jnp.asarray(np.asarray(h["w_packed"], np.float32)),
                  "T": int(h["T"]), "kdim": int(h["kdim"]),
                  "donors": class_fill.DonorIndex.build(src,
                                                        p.donor_counts()),
                  "interpret": interp}
        if obs.enabled():
            obs.gauge("repro_kernels_pallas_interpret_mode",
                      1.0 if interp else 0.0,
                      mode=interpret_mode_name(interp))

    def embed(self, plan, Yj, class_w):
        from repro.kernels.gee_scatter import gee_scatter_pallas
        d, cfg = plan.data, plan.config
        with obs.span("encoder.gather"):
            cls, route = self._classes(d, Yj)
        with obs.span("encoder.scatter"):
            Z = gee_scatter_pallas(d["rows"], cls, d["w"], num_tiles=d["T"],
                                   tile_n=cfg.tile_n, kdim=d["kdim"],
                                   interpret=d["interpret"])
            Z = _scale_columns(Z, class_w, n=plan.n_local)
        return Z, {"interpret": d["interpret"], "route": route}

    def _classes(self, d, Yj):
        """(each packed slot's class, the route that gave it): the
        labeled donors' classes written through the donor index while
        those writes cost less than a gather at every slot, else the
        gather.  Counts the route, and the fill's writes, per embed."""
        idx = d["donors"]
        if not isinstance(Yj, jax.core.Tracer):
            Y = np.asarray(Yj)
            sel = idx.labeled_rows(Y)
            writes = sel.size * idx.width
            if writes * class_fill.FILL_WRITE_COST < idx.slots:
                obs.counter("repro_kernel_class_fill_total",
                            backend=self.name, route="labeled")
                obs.counter("repro_kernel_class_writes_total", writes,
                            backend=self.name)
                return idx.fill(Y, sel, d["src"].shape), "labeled"
        obs.counter("repro_kernel_class_fill_total", backend=self.name,
                    route="gather")
        return Yj[d["src"]], "gather"

    def slots(self, plan):
        return int(plan.data["rows"].size)         # T * bpt * EB


@register_backend("streaming")
class StreamingBackend(Backend):
    """`gee_streaming`'s accumulate loop over bucket-padded chunks, with
    Wv built from the Embedder's class weights: bounded DEVICE working set — each chunk is
    uploaded, folded into Z, and released, so only O(chunk) edge data
    plus Z ever lives on device (the serving-rebuild and out-of-core
    ingestion path).  Chunks stay host-side in the plan (non-tail
    chunks are views of the caller's arrays, not copies; chunking is
    cheap, so only w_eff rides the persistent cache).

    Under a row partition the chunks are owned-destination
    contribution triples and the accumulator is (n_local, K) — device
    memory is O(chunk + n/p), the sharded serving rebuild path."""

    supports_row_partition = True

    def plan_host(self, graph, config, w_eff):
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

    def embed(self, plan, Yj, class_w):
        from repro.core.gee import gee_streaming, gee_streaming_owned
        cfg = plan.config
        Wv = make_w(Yj, cfg.K, class_w)
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


@register_backend("distributed:reduce_scatter")
class DistributedBackend(Backend):
    """SPMD over the edge mesh (`core.distributed`).

    The plan pads edges (at least one a chip) and rows to the mesh,
    places the padded arrays sharded over the mesh's edge axis and
    packs, inside the same span (``encoder.place``), each chip's
    contributions on the chips into the pallas kernel's (T, BPT, 1, EB)
    destination-tiled slots (`pack_sharded`: one sort, one read-back of
    the fullest tile, one scatter into the slots).  It keeps only the
    packed slots: like the pallas backend's, they are label-free, so no
    refit packs again, and padding and packing are per-process finalize
    work.

    Each embed replicates the labels and the Embedder's class weights
    over the mesh and dispatches one jitted program (span
    ``encoder.shard_embed``, `gee_packed`): it gathers each slot's
    label, runs the kernel on every chip into a full (n_pad, K) partial
    Z, reduces it with one psum_scatter and scales each row shard's
    columns by the class weights.  It counts the least bytes one chip
    must send in that collective
    (``repro_distributed_collective_bytes_total{mode="reduce_scatter"}``,
    `core.distributed.collective_bytes`; the lowered collective may send
    more).
    """

    def plan_finalize(self, p, graph, *, mesh=None):
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.core.distributed import (AXIS, collective_bytes,
                                            edge_mesh, pack_sharded,
                                            pad_rows)
        from repro.kernels.gee_scatter import resolve_interpret
        cfg = p.config
        mesh = mesh if mesh is not None else edge_mesh()
        nd = mesh.devices.size
        n_pad = pad_rows(graph.n, nd)
        s_pad = pad_rows(max(graph.s, 1), nd)
        g = Graph(np.asarray(graph.u), np.asarray(graph.v), p.w_eff,
                  graph.n).pad_to(s_pad)
        # straight from host memory: each chip receives only its s/p
        # edges, and no embed moves them again
        with obs.span("encoder.place", backend=self.name,
                      s=s_pad) as sp:
            edges = jax.device_put(
                (g.u, g.v, g.w), NamedSharding(mesh, PartitionSpec(AXIS)))
            edges = pack_sharded(*edges, n=n_pad, mesh=mesh,
                                 tile_n=cfg.tile_n,
                                 edge_block=cfg.edge_block)
            edges = sp.fence(edges)
        p.data = {"mesh": mesh, "n_pad": n_pad,
                  "collective_bytes": collective_bytes(n_pad, cfg.K, nd),
                  **dict(zip(("rows", "src", "w"), edges)),
                  "interpret": resolve_interpret(cfg.interpret)}

    def slots(self, plan):
        return int(plan.data["rows"].size)         # p * T * BPT * EB

    def embed(self, plan, Yj, class_w):
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.core.distributed import gee_packed
        d, cfg = plan.data, plan.config
        pad = d["n_pad"] - plan.n
        if pad:
            Yj = jnp.concatenate([Yj, jnp.full(pad, -1, jnp.int32)])
        Yj, class_w = jax.device_put(
            (Yj, class_w), NamedSharding(d["mesh"], PartitionSpec()))
        with obs.span("encoder.shard_embed", backend=self.name):
            Z = gee_packed(d["rows"], d["src"], d["w"], Yj, class_w,
                           K=cfg.K, n=d["n_pad"], mesh=d["mesh"],
                           tile_n=cfg.tile_n, interpret=d["interpret"])
        obs.counter("repro_distributed_collective_bytes_total",
                    d["collective_bytes"], mode="reduce_scatter")
        return (Z[:plan.n] if pad else Z), {}


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
