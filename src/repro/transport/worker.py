"""Worker process entry: host one shard (or one replica) over RPC.

``python -m repro.transport.worker`` is the multi-process deployment's
unit of execution.  Two roles:

* ``--role shard`` hosts one real `EmbeddingShard` (owned rows
  [lo, hi)) behind a `ShardHost` handler whose wire methods mirror the
  shard surface 1:1 — the router's `RemoteShard` proxy calls them with
  the exact arguments `ServingEngine` already produces, so the routing
  logic upstream is unchanged byte for byte.
* ``--role replica`` hosts a `ReplicaEngine` (transport.replica): a
  full read-only engine bootstrapped from the owner's snapshot and kept
  fresh by tailing its WAL, serving version-pinned reads.

Startup handshake: after binding, the worker prints one line —
``LISTENING <addr>`` — to stdout and then serves until a
``__shutdown__`` RPC (or SIGTERM).  Spawners bind port 0 and learn the
real address from that line.

Environment pinning: the spawner (`transport.procs`) stamps the
router's *effective* config into the child environment — ``REPRO_OBS``
(the router's live obs state, not just its env), ``REPRO_PLAN_CACHE``,
and ``JAX_PLATFORMS`` — and this module keeps its heavy imports inside
:func:`main`, after the environment is final, so a worker can never
diverge from the router on metrics, plan caching, or device selection.
Backend selection also honors ``REPRO_TRANSPORT_BACKEND`` as the flag
default for externally-launched workers (``serving.server
--serve-shard``).
"""
from __future__ import annotations

import argparse
import os
from typing import Optional

# deliberately light imports only (see module docstring): numpy + the
# transport layer; jax enters via the lazy imports in the host ctors
import numpy as np

from repro import obs
from repro.compile_cache import enable_compile_cache
from repro.transport.rpc import RpcServer, parse_addr


def _np(x, dtype=None, *, copy: bool = False):
    """Wire array -> numpy.  The codec's zero-copy decode yields
    read-only views; write-path inputs (anything the embedder folds)
    are copied so downstream in-place ops can never trip on them."""
    a = np.asarray(x) if dtype is None else np.asarray(x, dtype)
    return np.array(a) if copy else a


class ShardHost:
    """Wire-facing wrapper around one `EmbeddingShard`.

    Every method takes/returns codec-friendly values (numpy arrays,
    ints, dicts); device residency is the worker's private business —
    results cross the wire as host arrays and the router re-wraps them.
    """

    role = "shard"

    def __init__(self, shard_id: int, lo: int, hi: int, *, K: int,
                 n: int, chunk_size: int = 1 << 20,
                 backend: str = "streaming", plan_cache="auto"):
        from repro.serving.shard import EmbeddingShard
        self.backend = backend
        self.shard = EmbeddingShard(shard_id, lo, hi, K=K, n=n,
                                    chunk_size=chunk_size,
                                    backend=backend,
                                    plan_cache=plan_cache)

    def ping(self) -> dict:
        return {"role": self.role, "pid": os.getpid(),
                "shard_id": self.shard.shard_id,
                "lo": self.shard.lo, "hi": self.shard.hi,
                "backend": self.backend, "obs": obs.enabled()}

    # -- write path --------------------------------------------------------

    def build(self, u, v, w, n, fp: Optional[str], Y) -> int:
        """Fit on a routed sub-multiset.  `fp` is the router's chained
        sub-multiset fingerprint, stamped onto the materialized Graph so
        the worker's plan cache keys on the same content identity the
        in-process shard would — rebuilds stay (tier-2) cache hits."""
        from repro.graph.edges import Graph
        g = Graph(_np(u, np.int32, copy=True), _np(v, np.int32, copy=True),
                  _np(w, np.float32, copy=True), int(n))
        if fp is not None:
            g._fp = fp
        self.shard.build(g, _np(Y, np.int32, copy=True))
        return self.shard.accumulator_nbytes

    def apply_delta(self, u, v, w, n) -> None:
        from repro.graph.edges import Graph
        self.shard.apply_delta(
            Graph(_np(u, np.int32, copy=True), _np(v, np.int32, copy=True),
                  _np(w, np.float32, copy=True), int(n)))

    # -- read path ---------------------------------------------------------

    def z_owned(self):
        return np.asarray(self.shard.Z_owned)

    def accumulator_nbytes(self) -> int:
        return int(self.shard.accumulator_nbytes)

    def rows(self, nodes):
        return np.asarray(self.shard.rows(_np(nodes, np.int64)))

    def normalized(self):
        return np.asarray(self.shard.normalized())

    def class_stats(self, Y):
        sums, counts = self.shard.class_stats(_np(Y, np.int32))
        return [np.asarray(sums), np.asarray(counts)]

    def topk_candidates(self, q, qnodes, k, block_rows):
        import jax.numpy as jnp
        ids, vals = self.shard.topk_candidates(
            jnp.asarray(_np(q, np.float32)), _np(qnodes, np.int32),
            k=int(k), block_rows=int(block_rows))
        return [np.asarray(ids), np.asarray(vals)]

    # -- IVF index ---------------------------------------------------------

    def has_index(self) -> bool:
        return self.shard.index is not None

    def index_cell_sizes(self):
        return np.asarray(self.shard.index.cell_sizes())

    def build_index(self, centroids) -> None:
        self.shard.build_index(_np(centroids, np.float32, copy=True))

    def update_index(self, touched_global) -> int:
        return int(self.shard.update_index(
            _np(touched_global, np.int64, copy=True)))

    def index_topk(self, q, qnodes, probe, k, block_rows):
        import jax.numpy as jnp
        ids, vals, scanned = self.shard.index_topk(
            jnp.asarray(_np(q, np.float32)), _np(qnodes, np.int32),
            _np(probe, np.int32), k=int(k), block_rows=int(block_rows))
        return [np.asarray(ids), np.asarray(vals), int(scanned)]

    # -- introspection / p==1 compat ---------------------------------------

    def plan_stats(self) -> dict:
        return dict(self.shard.plan_stats)

    def embedder_Z(self):
        Z = self.shard.embedder.Z_
        return None if Z is None else np.asarray(Z)

    def embedder_Wv(self):
        Wv = self.shard.embedder.Wv_
        return None if Wv is None else np.asarray(Wv)


class ReplicaHost:
    """Wire-facing wrapper around one `ReplicaEngine`."""

    role = "replica"

    def __init__(self, data_dir: str, *, poll_s: float = 0.02,
                 chunk_size: int = 1 << 20, backend: str = "streaming",
                 plan_cache="auto"):
        from repro.transport.replica import ReplicaEngine
        self.backend = backend
        self.rep = ReplicaEngine(data_dir, poll_s=poll_s,
                                 chunk_size=chunk_size, backend=backend,
                                 plan_cache=plan_cache)

    def ping(self) -> dict:
        out = {"role": self.role, "pid": os.getpid(),
               "backend": self.backend, "obs": obs.enabled()}
        out.update(self.rep.status())
        return out

    def status(self) -> dict:
        return self.rep.status()

    def embed(self, nodes, min_version):
        return self.rep.embed(_np(nodes, np.int64),
                              min_version=int(min_version))

    def predict(self, nodes, min_version):
        pred, score = self.rep.predict(_np(nodes, np.int64),
                                       min_version=int(min_version))
        return [pred, score]

    def topk(self, nodes, k, block_rows, mode, nprobe, min_version):
        idx, val = self.rep.topk(
            _np(nodes, np.int64), k=int(k), block_rows=int(block_rows),
            mode=str(mode),
            nprobe=(int(nprobe) if nprobe is not None else None),
            min_version=int(min_version))
        return [idx, val]


def _parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(
        prog="repro.transport.worker",
        description="host one EmbeddingShard or WAL-tail replica "
                    "over RPC")
    ap.add_argument("--role", choices=["shard", "replica"],
                    required=True)
    ap.add_argument("--addr", default="127.0.0.1:0",
                    help="HOST:PORT (port 0 = ephemeral; the real "
                         "address is printed as 'LISTENING <addr>') "
                         "or unix:PATH")
    ap.add_argument("--backend",
                    default=os.environ.get("REPRO_TRANSPORT_BACKEND",
                                           "streaming"))
    ap.add_argument("--plan-cache", default="auto",
                    help="'auto', 'off', or a cache dir")
    ap.add_argument("--chunk-size", type=int, default=1 << 20)
    ap.add_argument("--obs", choices=["on", "off"], default=None,
                    help="override the inherited REPRO_OBS state")
    # shard role
    ap.add_argument("--shard-id", type=int, default=0)
    ap.add_argument("--lo", type=int, default=None)
    ap.add_argument("--hi", type=int, default=None)
    ap.add_argument("--classes", type=int, default=None,
                    help="K, the embedding width")
    ap.add_argument("--nodes", type=int, default=None,
                    help="n, the global row count")
    # replica role
    ap.add_argument("--data-dir", default=None)
    ap.add_argument("--poll-ms", type=float, default=20.0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = _parse(argv)
    enable_compile_cache()
    if args.obs is not None:             # explicit flag wins over env
        obs.configure(enabled=(args.obs == "on"))
    plan_cache = (None if args.plan_cache in ("off", "none")
                  else args.plan_cache)
    if args.role == "shard":
        for name in ("lo", "hi", "classes", "nodes"):
            if getattr(args, name) is None:
                raise SystemExit(f"--role shard requires --{name}")
        handler = ShardHost(args.shard_id, args.lo, args.hi,
                            K=args.classes, n=args.nodes,
                            chunk_size=args.chunk_size,
                            backend=args.backend, plan_cache=plan_cache)
    else:
        if args.data_dir is None:
            raise SystemExit("--role replica requires --data-dir")
        handler = ReplicaHost(args.data_dir,
                              poll_s=args.poll_ms / 1e3,
                              chunk_size=args.chunk_size,
                              backend=args.backend,
                              plan_cache=plan_cache)
    addr = parse_addr(args.addr)
    if isinstance(addr, str):
        server = RpcServer(handler, path=addr)
    else:
        server = RpcServer(handler, host=addr[0], port=addr[1])
    # the spawner's handshake: exactly one line, then silence
    print(f"LISTENING {server.address}", flush=True)
    server.serve_forever()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
