"""Chip smoke run: GEE's main path once, on a TPU, through the entry
points a user calls, at a real deployment size.

    python3 chip_smoke.py              # one chip: batch + serving phases
    python3 chip_smoke.py --chips 4    # four chips: the multi-chip fit only
    python3 chip_smoke.py --tiny       # the same phases at n ~ 2,000;
                                       # the only mode that accepts CPU

Default phases, on one chip:

* batch — an SBM graph at the soc-pokec shape (`configs.base.
  PAPER_GRAPHS`: n = 1.6M, s = 30M), K = 50, 10% labeled.
  ``Embedder(EncoderConfig(K=50))`` with the default ``auto`` backend
  must resolve to the compiled pallas kernel.  It runs fit, refit
  under churned labels, and 3 refine rounds; the fit Z is compared
  with the ``xla`` backend on the same chip and with the numpy oracle.
* serving — two `ServingEngine`s behind `MicroBatcher` answer one
  request stream: a durable 2-shard pallas engine (owned-rows fit,
  fused delta + renormalize, fused top-k) and the default streaming
  engine.  Their answers must agree; the delta-maintained Z must match
  a rebuild; the WAL must recover the exact (version, epoch,
  fingerprint); the flush loop must have recorded no error.

``--chips 4`` runs only the four-chip path: ``auto`` on four devices
resolves to ``distributed:reduce_scatter``, at the soc-orkut shape
(3M nodes, 117M edges, K = 50), compared with the numpy oracle.

Times printed are smoke timings of one cold run, compilation
included — not benchmarks.  Any failed check raises, so the script
exits nonzero before its last line, which is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

#: Z entries are sums of positive terms (edge weight x 1/class count),
#: so there is no cancellation: two f32 summation orders over d terms
#: differ by at most ~d * 2^-24 relative, under 1e-5 for the degrees
#: here (< 170).  One bf16 rounding of the values (2^-9 ~ 2e-3) fails.
Z_RTOL = 1e-5
#: served Z is delta-maintained, and a delete subtracts what an insert
#: added: a cancelled entry keeps a residue of a few ulps of the
#: largest partial sum, hence an absolute floor relative to max |Z|
SERVE_ATOL_REL = 1e-6
#: top-k scores are cosines (scale 1) of rows that agree to Z_RTOL;
#: both engines score at f32 precision, so scores agree far inside
#: this, while a bf16-rounded score would be off by ~4e-3
TOPK_ATOL = 1e-5

#: (n, s) per mode: the published shapes and the rehearsal cut, which
#: keeps the mean degree (2s / n = 37.5 for soc-pokec)
SHAPES = {
    ("one", False): ("soc-pokec", None),
    ("one", True): ("soc-pokec", (2_000, 37_500)),
    ("four", False): ("soc-orkut", None),
    ("four", True): ("soc-orkut", (4_000, 156_000)),
}


def check(name: str, ok: bool, detail: str = "") -> None:
    print(f"[check] {name}: {'PASS' if ok else 'FAIL'} {detail}".rstrip(),
          flush=True)
    if not ok:
        raise AssertionError(f"{name} {detail}")


@contextlib.contextmanager
def phase(name: str):
    """Wall time of one phase, printed as a smoke timing."""
    t0 = time.perf_counter()
    yield
    print(f"[phase] {name}: {time.perf_counter() - t0:.3f} s "
          "(smoke timing, cold, compile included)", flush=True)


def z_close(name: str, Z, ref, *, rtol: float = Z_RTOL,
            atol: float = 0.0) -> None:
    Z, ref = np.asarray(Z), np.asarray(ref)
    ok = Z.shape == ref.shape and bool(np.isfinite(Z).all())
    err = float(np.max(np.abs(Z - ref))) if ok and Z.size else 0.0
    if ok:
        ok = bool(np.allclose(Z, ref, rtol=rtol, atol=atol))
    check(name, ok, f"shape={Z.shape} max|dZ|={err:.3e} "
                    f"max|Z|={float(np.max(np.abs(ref))):.3e} "
                    f"rtol={rtol:g} atol={atol:.3e}")


def make_graph(spec_name: str, cut, seed: int):
    from repro.configs.base import PAPER_GRAPHS
    from repro.graph.edges import make_labels
    from repro.graph.sources import SyntheticSource
    spec = PAPER_GRAPHS[spec_name]
    n, s = cut if cut is not None else (spec.n, spec.s)
    src = SyntheticSource("sbm", n=n, K=spec.K, s=s, seed=seed)
    g = src.graph()
    rng = np.random.default_rng(seed)
    Y = make_labels(n, spec.K, spec.labeled_frac, rng,
                    true_labels=src.labels)
    print(f"[graph] {spec_name}{' (tiny cut)' if cut else ''}: "
          f"sbm n={n:,} s={s:,} K={spec.K} "
          f"labeled={int((Y >= 0).sum()):,}", flush=True)
    return src, g, Y, src.labels, spec.K


def oracle(g, Y, K: int):
    from repro.core.ref_python import gee_numpy
    return gee_numpy(np.asarray(g.u), np.asarray(g.v), np.asarray(g.w),
                     Y, K, g.n)


# -- batch phase -------------------------------------------------------------

def batch_phase(src, g, Y, truth, K: int, platform: str, seed: int):
    import jax
    from repro.encoder import Embedder, EncoderConfig
    cfg = EncoderConfig(K=K, refine_iters=3)
    emb = Embedder(cfg, plan_cache=None)
    with phase("batch.fit (auto)"):
        emb.fit(src, Y)
        jax.block_until_ready(emb.Z_)
    want = "pallas" if platform == "tpu" else "xla"
    check("auto backend", emb.backend.name == want,
          f"resolved={emb.backend.name} expected={want}")
    if emb.backend.name == "pallas":
        check("pallas compiled", emb._plan.data["interpret"] is False,
              f"interpret={emb._plan.data['interpret']}")
    with phase("batch.oracle (numpy, host)"):
        Z_ref = oracle(g, Y, K)
    z_close(f"fit Z[{emb.backend.name}] vs gee_numpy", emb.Z_, Z_ref)
    # the other device backends on the same chip (on CPU, where auto is
    # xla, this also runs the pallas kernel in interpret mode)
    others = {}
    for name in ("xla", "pallas"):
        if name == emb.backend.name:
            continue
        with phase(f"batch.fit ({name})"):
            other = Embedder(cfg, backend=name, plan_cache=None)
            other.fit(src, Y)
            jax.block_until_ready(other.Z_)
        z_close(f"fit Z[{emb.backend.name}] vs {name}", emb.Z_, other.Z_)
        others[name] = other

    # refit under churned labels: reveal 1% more true labels and flip
    # 1% of the known ones; the cached plan is reused (no host packing)
    rng = np.random.default_rng(seed + 1)
    Y2 = Y.copy()
    m = max(1, g.n // 100)
    reveal = rng.choice(g.n, m, replace=False)
    Y2[reveal] = truth[reveal]
    known = np.flatnonzero(Y2 >= 0)
    flip = rng.choice(known, max(1, known.size // 100), replace=False)
    Y2[flip] = rng.integers(0, K, flip.size)
    built = emb.plan_stats["built"]
    with phase("batch.refit (churned labels)"):
        emb.refit(Y2)
        jax.block_until_ready(emb.Z_)
    check("refit reused the plan", emb.plan_stats["built"] == built,
          f"plan_stats={emb.plan_stats}")
    ref_emb = others["xla"] if "xla" in others else None
    if ref_emb is None:
        z_close("refit Z vs gee_numpy", emb.Z_, oracle(g, Y2, K))
    else:
        ref_emb.refit(Y2)
        z_close("refit Z vs xla refit", emb.Z_, ref_emb.Z_)

    with phase("batch.refine (3 rounds)"):
        emb.refine(jax.random.PRNGKey(seed))
        jax.block_until_ready(emb.Z_)
    lab = np.asarray(emb.labels_)
    pinned = Y2 >= 0
    check("refine keeps supervised labels",
          bool(np.array_equal(lab[pinned], Y2[pinned])),
          f"pinned={int(pinned.sum()):,}")
    check("refine labels in range",
          bool(lab.min() >= 0 and lab.max() < K), "")
    # the refined Z is the embedding under the refined labels
    check_emb = ref_emb if ref_emb is not None else Embedder(
        cfg, backend="numpy", plan_cache=None).fit(g, Y2)
    check_emb.refit(lab)
    z_close("refine Z vs re-embed under its labels", emb.Z_,
            check_emb.Z_)


# -- serving phase -----------------------------------------------------------

def request_stream(n: int, K: int, truth, *, ticks: int, reads: int,
                   read_nodes: int, insert: int, seed: int):
    """One tick = `reads` reads (embed / predict / top-k) of
    `read_nodes` nodes, one `insert`-edge batch, sometimes a delete of
    an earlier batch and a label reveal; one checkpoint mid-stream."""
    rng = np.random.default_rng(seed)
    stream, inserted = [], []
    kinds = ("embed", "predict", "topk")
    for t in range(ticks):
        ops = [(kinds[(t + i) % 3],
                rng.integers(0, n, read_nodes).astype(np.int32))
               for i in range(reads)]
        u = rng.integers(0, n, insert).astype(np.int32)
        v = rng.integers(0, n, insert).astype(np.int32)
        w = (rng.random(insert) + 0.5).astype(np.float32)
        ops.append(("insert", (u, v, w)))
        inserted.append((u, v, w))
        if len(inserted) > 3 and rng.random() < 0.4:
            ops.append(("delete",
                        inserted.pop(int(rng.integers(0, len(inserted))))))
        if rng.random() < 0.3:
            nodes = rng.choice(n, max(1, n // 100), replace=False)
            ops.append(("labels", (nodes, truth[nodes])))
        stream.append((ops, t == ticks // 2))
    return stream


def run_engine(label: str, g, Y, K: int, stream, *, backend: str,
               shards: int, data_dir):
    import jax
    from repro.serving.batcher import MicroBatcher
    from repro.serving.engine import ServingEngine
    from repro.serving.store import GraphStore
    with phase(f"serving.{label}.boot"):
        eng = ServingEngine(GraphStore(g, Y, K), num_shards=shards,
                            backend=backend, data_dir=data_dir,
                            plan_cache=None)
        jax.block_until_ready(eng.Z)
    batcher = MicroBatcher(eng, topk=10)
    eng.start(batcher)
    answers = []
    with phase(f"serving.{label}.stream ({len(stream)} ticks)"):
        for ops, checkpoint in stream:
            tickets = [(kind, batcher.submit(kind, payload))
                       for kind, payload in ops]
            for kind, t in tickets:
                out = t.result(timeout=600)
                if kind in ("embed", "predict", "topk"):
                    answers.append((kind, out))
            if checkpoint:
                if data_dir is not None:
                    eng.checkpoint()
                else:
                    eng.compact()
    eng.stop()
    check(f"{label} flush loop error", eng.loop_error is None,
          f"loop_error={eng.loop_error!r}")
    health = eng.health()
    check(f"{label} health", health["state"] == "serving", str(health))
    print(f"[serving] {label}: version={eng.version} epoch={eng.epoch} "
          f"rebuilds={eng.rebuilds} checkpoints={eng.checkpoints}",
          flush=True)
    return eng, answers


def _topk_equivalent():
    """The test suite's tie-tolerant top-k assertion
    (`tests/conftest.py:topk_equivalent`)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_smoke_conftest", os.path.join(HERE, "tests", "conftest.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.topk_equivalent


def serving_phase(g, Y, truth, K: int, tiny: bool, seed: int):
    from repro.serving.engine import ServingEngine
    from repro.serving.server import delta_rebuild_gap
    topk_equivalent = _topk_equivalent()

    stream = request_stream(g.n, K, truth, ticks=20, reads=8,
                            read_nodes=64,
                            insert=200 if tiny else 10_000, seed=seed)
    tmp = tempfile.mkdtemp(prefix="chip-smoke-")
    try:
        ref, ref_ans = run_engine("streaming", g, Y, K, stream,
                                  backend="streaming", shards=1,
                                  data_dir=None)
        Z_ref = np.asarray(ref.Z)
        ref.close()
        del ref
        data_dir = os.path.join(tmp, "pallas")
        eng, ans = run_engine("pallas", g, Y, K, stream,
                              backend="pallas", shards=2,
                              data_dir=data_dir)
        scale = float(np.max(np.abs(Z_ref)))
        atol = SERVE_ATOL_REL * scale
        check("same reads answered",
              [k for k, _ in ans] == [k for k, _ in ref_ans],
              f"{len(ans)} vs {len(ref_ans)}")
        reads = {"embed": 0, "predict": 0, "topk": 0}
        bad = {"embed": 0, "predict": 0}
        for (kind, a), (_, b) in zip(ans, ref_ans):
            reads[kind] += 1
            if kind == "embed":
                bad[kind] += not np.allclose(a, b, rtol=Z_RTOL, atol=atol)
            elif kind == "predict":
                bad[kind] += not np.array_equal(a[0], b[0])
            else:                        # raises on a mismatch
                topk_equivalent(a[0], a[1], b[0], b[1], atol=TOPK_ATOL)
        check("engines agree", not any(bad.values()),
              f"reads={reads} mismatched={bad} embed rtol={Z_RTOL:g} "
              f"atol={atol:.3e}, predict equal, top-k tie-tolerant "
              f"atol={TOPK_ATOL:g}")
        Z_live = np.asarray(eng.Z)
        z_close("pallas engine Z vs streaming engine Z", Z_live, Z_ref,
                atol=atol)
        gap = delta_rebuild_gap(eng)
        check("delta-maintained Z vs rebuild", gap <= atol + Z_RTOL * scale,
              f"max|dZ|={gap:.3e} bound={atol + Z_RTOL * scale:.3e}")
        triple = (eng.version, eng.epoch, eng.fingerprint())
        eng.close()
        del eng
        with phase("serving.pallas.recover (WAL replay + rebuild)"):
            rec = ServingEngine.open(data_dir, backend="pallas",
                                     plan_cache=None)
            Z_rec = np.asarray(rec.Z)
        rtriple = (rec.version, rec.epoch, rec.fingerprint())
        check("WAL recovery (version, epoch, fingerprint)",
              rtriple == triple, f"{rtriple} vs live {triple}")
        z_close("recovered Z vs live Z", Z_rec, Z_live, atol=atol)
        check("recovered health", rec.health()["state"] == "serving", "")
        rec.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


# -- four chips --------------------------------------------------------------

def four_chip_phase(src, g, Y, K: int):
    import jax
    from repro.encoder import Embedder, EncoderConfig
    emb = Embedder(EncoderConfig(K=K), plan_cache=None)
    with phase("four.fit (auto)"):
        emb.fit(src, Y)
        jax.block_until_ready(emb.Z_)
    check("auto backend", emb.backend.name == "distributed:reduce_scatter",
          f"resolved={emb.backend.name} devices={len(jax.devices())}")
    with phase("four.oracle (numpy, host)"):
        Z_ref = oracle(g, Y, K)
    z_close("fit Z[distributed:reduce_scatter] vs gee_numpy", emb.Z_,
            Z_ref)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="n ~ 2,000 rehearsal; accepts CPU")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src_dir = os.path.join(HERE, "src")
    if src_dir not in sys.path:
        sys.path.insert(0, src_dir)
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"[device] {dev} compile_cache={cache}", flush=True)
    if dev["platform"] != "tpu" and not args.tiny:
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{dev['platform']!r}); --tiny rehearses on CPU",
              file=sys.stderr)
        return 2
    if dev["count"] != args.chips:
        print(f"chip_smoke: --chips {args.chips} needs exactly "
              f"{args.chips} device(s), found {dev['count']}",
              file=sys.stderr)
        return 2

    mode = "four" if args.chips == 4 else "one"
    spec_name, cut = SHAPES[(mode, args.tiny)]
    with phase("setup.graph (host generation)"):
        src, g, Y, truth, K = make_graph(spec_name, cut, args.seed)
    if mode == "four":
        four_chip_phase(src, g, Y, K)
    else:
        batch_phase(src, g, Y, truth, K, dev["platform"], args.seed)
        serving_phase(g, Y, truth, K, args.tiny, args.seed)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
