import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax import anywhere: jax locks
# the device count on first init, and the dry-run needs 512 placeholder
# host devices to build the production meshes.  (Tests and benches never
# import this module, so they keep seeing 1 device.)
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell the REAL step function (the same code the trainer/server
executes) is jitted with explicit shardings and compiled for the
16x16=256-chip single-pod mesh and the 2x16x16=512-chip multi-pod mesh.
``compiled.memory_analysis()`` proves the cell fits; ``cost_analysis()``
+ HLO collective parsing feed EXPERIMENTS.md §Roofline.

Usage:
    python -m repro.launch.dryrun --arch yi-9b --shape train_4k
    python -m repro.launch.dryrun --all                 # single-pod sweep
    python -m repro.launch.dryrun --all --multi-pod     # 512-chip sweep
    python -m repro.launch.dryrun --gee                 # paper workload
Results land in artifacts/dryrun/<mesh>/<arch>__<shape>.json.
"""
import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_shape, list_archs
from repro.launch import roofline as RL
from repro.launch.mesh import make_gee_mesh, make_production_mesh
from repro.models import model as M
from repro.sharding import make_rules, use_sharding
from repro.training.optimizer import AdamW
from repro.training.train_loop import make_train_step

ART = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                   "artifacts", "dryrun")


def _sharded_abstract(tree_specs, rules):
    """ParamSpec tree -> ShapeDtypeStruct-with-sharding tree."""
    from repro.models.layers import tree_map_specs
    return tree_map_specs(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype or jnp.dtype("float32"),
            sharding=rules.named(rules.weight_spec(s.shape, s.logical))),
        tree_specs)


def _batch_abstract(cfg, shape, rules):
    B, S = shape.global_batch, shape.seq_len
    bsh = rules.named(rules.act_spec((B, S), ("batch", "seq")))
    out = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=bsh)}
    if cfg.is_encdec:
        fsh = rules.named(rules.act_spec(
            (B, cfg.n_frames, cfg.d_model), ("batch", "seq", "embed")))
        out["frames"] = jax.ShapeDtypeStruct(
            (B, cfg.n_frames, cfg.d_model), jnp.dtype(cfg.compute_dtype),
            sharding=fsh)
    return out


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
               impl: str = "flash", fsdp: bool = True,
               seq_shard_acts: bool = False, accum_steps: int = 1,
               compress_grads: bool = False,
               cfg_override=None, shape_override=None,
               compile_it: bool = True, compiler_options=None):
    """Returns (lowered, compiled, mesh, cfg, shape)."""
    cfg = cfg_override if cfg_override is not None else get_config(arch)
    shape = shape_override if shape_override is not None \
        else get_shape(shape_name)
    if cfg_override is None and shape_name not in \
            [s.name for s in cfg.shapes()]:
        raise ValueError(f"{arch} skips {shape_name} "
                         f"(sub_quadratic={cfg.sub_quadratic})")
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = make_rules(mesh, fsdp=fsdp, seq_shard_acts=seq_shard_acts)

    with use_sharding(mesh, rules):
        pspecs = M.param_specs(cfg)
        params_abs = _sharded_abstract(pspecs, rules)

        if shape.kind == "train":
            opt = AdamW(state_dtype=cfg.state_dtype,
                        clip_norm=float(os.environ.get("DRYRUN_CLIP",
                                                       "1.0")))
            step = make_train_step(cfg, opt, impl=impl,
                                   accum_steps=accum_steps,
                                   compress_grads=compress_grads)
            opt_abs = opt.init_abstract(params_abs)
            # opt moments share the param shardings; step is replicated
            batch_abs = _batch_abstract(cfg, shape, rules)
            lowered = jax.jit(step, donate_argnums=(0, 1)).lower(
                params_abs, opt_abs, batch_abs)
        elif shape.kind == "prefill":
            def prefill_fn(params, batch):
                return M.prefill(cfg, params, batch, impl=impl)
            batch_abs = _batch_abstract(cfg, shape, rules)
            lowered = jax.jit(prefill_fn).lower(params_abs, batch_abs)
        else:  # decode
            def serve_step(params, token, pos, cache):
                return M.decode_step(cfg, params, token, pos, cache)
            B = shape.global_batch
            tok = jax.ShapeDtypeStruct(
                (B,), jnp.int32,
                sharding=rules.named(rules.act_spec((B,), ("batch",))))
            pos = jax.ShapeDtypeStruct((), jnp.int32)
            cache_specs = M.cache_specs(cfg, B, shape.seq_len)
            cache_abs = _sharded_abstract(cache_specs, rules)
            lowered = jax.jit(serve_step, donate_argnums=(3,)).lower(
                params_abs, tok, pos, cache_abs)

        if compile_it:
            compiled = (lowered.compile(compiler_options)
                        if compiler_options else lowered.compile())
        else:
            compiled = None
    return lowered, compiled, mesh, cfg, shape


def _probe_costs(arch, shape_name, *, multi_pod, impl, fsdp,
                 seq_shard_acts, accum_steps, compress_grads=False):
    """Differential depth probes (see launch/analytic.py): lower the cell
    at unit and 2x-unit depth with all scans unrolled, returning the
    extrapolated full-depth {flops, bytes, coll_*} dict.

    Probe lowerings use remat=False + backend opt level 0 (compile-time
    economy on the 1-core host); for remat'd train cells the flops are
    corrected by 4/3 (full recompute re-runs the forward: fwd+bwd = 3
    units -> remat adds 1).  xlstm prefill probes run at seq 4096 and
    scale linearly (attention-free family: every term is T-linear)."""
    import dataclasses as _dc

    from repro.launch import analytic
    from repro.models import unrollctl

    cfg = get_config(arch)
    shape = get_shape(shape_name)
    cfg_u, cfg_2u, n_units, tail_units = analytic.probe_unit(cfg)
    cfg_u = _dc.replace(cfg_u, remat=False)
    cfg_2u = _dc.replace(cfg_2u, remat=False)

    shape_probe, seq_scale = shape, 1.0
    if cfg.xlstm is not None and shape.kind == "prefill" \
            and shape.seq_len > 4096:
        shape_probe = _dc.replace(shape, seq_len=4096)
        seq_scale = shape.seq_len / 4096.0

    def cost_of(c):
        with unrollctl.unrolled():
            _, compiled, _, _, _ = lower_cell(
                arch, shape_name, multi_pod=multi_pod, impl=impl,
                fsdp=fsdp, seq_shard_acts=seq_shard_acts,
                accum_steps=accum_steps, compress_grads=compress_grads,
                cfg_override=c, shape_override=shape_probe,
                compiler_options={"xla_backend_optimization_level": "0"})
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        colls = RL.parse_collectives(compiled.as_text())
        out = {"flops": float(ca.get("flops", 0.0)),
               "bytes": float(ca.get("bytes accessed", 0.0))}
        for kind, v in colls.items():
            out[f"coll_{kind}"] = v["wire_bytes"]
        out["coll_total"] = sum(v["wire_bytes"] for v in colls.values())
        return out

    ext = analytic.extrapolate(cost_of(cfg_u), cost_of(cfg_2u),
                               n_units, tail_units)
    if seq_scale != 1.0:
        ext = {k: v * seq_scale for k, v in ext.items()}
    if shape.kind == "train" and cfg.remat:
        ext["flops"] *= 4.0 / 3.0       # remat recompute correction
    ext["flops"] += analytic.slstm_correction_flops(cfg, shape)
    return ext


def run_cell(arch, shape_name, *, multi_pod=False, impl="flash",
             fsdp=True, seq_shard_acts=False, accum_steps=1,
             compress_grads=False, save=True, tag="", probe=True):
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    t0 = time.time()
    lowered, compiled, mesh, cfg, shape = lower_cell(
        arch, shape_name, multi_pod=multi_pod, impl=impl, fsdp=fsdp,
        seq_shard_acts=seq_shard_acts, accum_steps=accum_steps,
        compress_grads=compress_grads)
    dt = time.time() - t0
    chips = int(np.prod(list(mesh.shape.values())))
    rl = RL.build(arch, shape, mesh_name, chips, compiled, cfg)
    rec = rl.to_dict()
    rec["raw_scan_counted"] = {          # undercounted (scan body once)
        "flops": rl.flops_per_device, "bytes": rl.bytes_per_device,
        "collective_bytes": rl.collective_bytes}

    if probe:
        # replace the scan-undercounted terms with depth-probe totals
        t1 = time.time()
        ext = _probe_costs(arch, shape_name, multi_pod=multi_pod,
                           impl=impl, fsdp=fsdp,
                           seq_shard_acts=seq_shard_acts,
                           accum_steps=accum_steps,
                           compress_grads=compress_grads)
        rl.flops_per_device = ext["flops"]
        rl.bytes_per_device = ext["bytes"]
        rl.collective_bytes = ext["coll_total"]
        rec.update(rl.to_dict())
        rec["probe"] = ext
        rec["probe_s"] = time.time() - t1

    rec["compile_s"] = dt
    rec["impl"] = impl
    rec["fsdp"] = fsdp
    rec["tag"] = tag
    ma = compiled.memory_analysis()
    rec["memory_analysis"] = {
        k: getattr(ma, k) for k in
        ("argument_size_in_bytes", "output_size_in_bytes",
         "temp_size_in_bytes", "alias_size_in_bytes")}
    if save:
        d = os.path.join(ART, mesh_name)
        os.makedirs(d, exist_ok=True)
        fn = f"{arch}__{shape_name}{('__' + tag) if tag else ''}.json"
        with open(os.path.join(d, fn), "w") as f:
            json.dump(rec, f, indent=1)
    print(f"[dryrun] {mesh_name} {arch:18s} {shape_name:12s} "
          f"compile={dt:6.1f}s flops/dev={rl.flops_per_device:.3e} "
          f"bytes/dev={rl.bytes_per_device:.3e} "
          f"coll/dev={rl.collective_bytes:.3e} dom={rl.dominant:10s} "
          f"args+tmp={(rl.arg_bytes + rl.temp_bytes)/1e9:7.2f}GB "
          f"mfu={rl.mfu:.3f}")
    return rec


# ---------------------------------------------------------------------------
# GEE (the paper's own workload) at Friendster scale
# ---------------------------------------------------------------------------


def run_gee(*, multi_pod=False, n=65_000_000, s=1_800_000_000, K=50,
            save=True):
    """Lower and compile `distributed:reduce_scatter`'s per-step
    program (`gee_packed`) over each chip's packed (T, BPT, 1, EB)
    slots.  Packing runs once per plan and its fullest tile fixes BPT,
    which needs the graph: the dry run takes the mean tile's."""
    from repro.core.distributed import AXIS, gee_packed
    from repro.core.gee import class_weights
    from repro.kernels.gee_scatter import EDGE_BLOCK, TILE_N
    mesh = make_gee_mesh(multi_pod=multi_pod)
    p = mesh.shape[AXIS]
    n_pad = ((n + p - 1) // p) * p
    s_pad = ((s + p - 1) // p) * p
    espec = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(AXIS))
    rspec = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    Y = jax.ShapeDtypeStruct((n_pad,), jnp.int32, sharding=rspec)
    T = -(-n_pad // TILE_N)
    bpt = -(-2 * (s_pad // p) // (T * EDGE_BLOCK))
    shape = (p * T, bpt, 1, EDGE_BLOCK)
    si = jax.ShapeDtypeStruct(shape, jnp.int32, sharding=espec)
    sf = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=espec)

    def fn(rows, src, w, Y):
        return gee_packed(rows, src, w, Y, class_weights(Y, K), K=K,
                          n=n_pad, mesh=mesh)

    t0 = time.time()
    lowered = jax.jit(fn).lower(si, si, sf, Y)
    compiled = lowered.compile()
    dt = time.time() - t0

    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    colls = RL.parse_collectives(compiled.as_text())
    bytes_dev = float(ca.get("bytes accessed", 0.0))
    wire = sum(c["wire_bytes"] for c in colls.values())
    ma = compiled.memory_analysis()
    chip = RL.peaks(RL.DRYRUN_KIND)
    mesh_name = ("pod2x16x16" if multi_pod else "pod16x16")
    rec = {
        "arch": "gee-friendster", "shape": "gee_reduce_scatter",
        "mesh": mesh_name,
        "chips": p, "compile_s": dt,
        "flops_per_device": float(ca.get("flops", 0.0)),
        "bytes_per_device": bytes_dev,
        "collective_bytes": wire, "collectives": colls,
        "compute_s": float(ca.get("flops", 0.0)) / chip.flops,
        "memory_s": bytes_dev / chip.hbm_bw,
        "collective_s": wire / chip.ici_bw,
        "arg_bytes": ma.argument_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "model_edges": s,
    }
    rec["dominant"] = max(("compute_s", "memory_s", "collective_s"),
                          key=lambda k: rec[k]).replace("_s", "")
    if save:
        d = os.path.join(ART, mesh_name)
        os.makedirs(d, exist_ok=True)
        with open(os.path.join(d, "gee__reduce_scatter.json"), "w") as f:
            json.dump(rec, f, indent=1)
    print(f"[dryrun] {mesh_name} gee-friendster reduce_scatter "
          f"compile={dt:6.1f}s flops/dev={rec['flops_per_device']:.3e} "
          f"bytes/dev={rec['bytes_per_device']:.3e} "
          f"coll/dev={wire:.3e} dom={rec['dominant']} "
          f"args+tmp={(rec['arg_bytes'] + rec['temp_bytes'])/1e9:7.2f}GB")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--gee", action="store_true")
    ap.add_argument("--impl", default="flash",
                    choices=["flash", "triangular", "full"])
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--seq-shard-acts", action="store_true")
    ap.add_argument("--accum-steps", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    failures = []
    if args.gee:
        try:
            run_gee(multi_pod=args.multi_pod)
        except Exception as e:
            traceback.print_exc()
            failures.append(("gee", "reduce_scatter", repr(e)))
    elif args.all:
        for arch in list_archs():
            cfg = get_config(arch)
            for shape in cfg.shapes():
                try:
                    # probes (roofline terms) are a single-pod deliverable;
                    # the multi-pod pass proves the pod axis shards.
                    run_cell(arch, shape.name, multi_pod=args.multi_pod,
                             impl=args.impl, fsdp=not args.no_fsdp,
                             seq_shard_acts=args.seq_shard_acts,
                             accum_steps=args.accum_steps, tag=args.tag,
                             probe=not args.multi_pod)
                except Exception as e:
                    traceback.print_exc()
                    failures.append((arch, shape.name, repr(e)))
            for skipped in cfg.skipped_shapes():
                print(f"[dryrun] SKIP {arch} {skipped} "
                      f"(full attention; see DESIGN.md §Arch-applicability)")
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        run_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                 impl=args.impl, fsdp=not args.no_fsdp,
                 seq_shard_acts=args.seq_shard_acts,
                 accum_steps=args.accum_steps,
                 compress_grads=args.compress_grads, tag=args.tag)

    if failures:
        print(f"[dryrun] {len(failures)} FAILURES:")
        for f in failures:
            print("   ", f)
        sys.exit(1)
    print("[dryrun] OK")


if __name__ == "__main__":
    main()
