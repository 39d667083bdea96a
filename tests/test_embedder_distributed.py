"""The Embedder's four-device path on 4 host devices (subprocess so the
device-count flag never leaks into other tests): `auto` resolves to
`distributed:reduce_scatter`, the plan packs each device's edges on the
devices into the scatter kernel's slots exactly as the host packer
would, every refit dispatches one cached program, the work and
collective counters equal hand arithmetic, and Z matches the serial
oracle."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
P_DEV = 4
K = 7

SCRIPT = r"""
import json, sys
import numpy as np, jax
sys.path.insert(0, %(tests)r)
from test_encoder import CFG, _cases
from repro import obs
from repro.core import ref_python as R
from repro.core.distributed import edge_mesh, gee_distributed
from repro.encoder import Embedder, EncoderConfig
from repro.encoder.plan import effective_weights
from repro.graph.edges import Graph, make_labels
from repro.graph.generators import erdos_renyi
from repro.kernels.ops import pack_edges

K, p = %(K)d, %(p)d
obs.configure(enabled=True)
r = obs.registry()
out = {"devices": len(jax.devices())}


def counts(backend, mode):
    return [r.counter_value("repro_kernel_slots_total", backend=backend),
            r.counter_value("repro_kernel_contributions_total",
                            backend=backend, donor="labeled"),
            r.counter_value("repro_kernel_contributions_total",
                            backend=backend, donor="unlabeled"),
            r.counter_value("repro_distributed_collective_bytes_total",
                            mode=mode)]


# auto on n = 1003, s = 20007 (both padded to the mesh): a fit, two refits
g = erdos_renyi(1003, 20007, seed=1, weighted=True)
rng = np.random.default_rng(0)
Ys = [make_labels(g.n, K, f, rng) for f in (0.2, 0.3, 0.1)]
emb = Embedder(EncoderConfig(K=K), plan_cache=None)
errs, compiles, cnt = [], [], []
for i, Y in enumerate(Ys):
    c0 = r.counter_value("repro_jax_compiles_total")
    emb.fit(g, Y) if i == 0 else emb.refit(Y)
    Z = emb.transform()
    compiles.append(r.counter_value("repro_jax_compiles_total") - c0)
    errs.append(float(np.abs(Z - R.gee_numpy(g.u, g.v, g.w, Y, K, g.n)).max()))
    cnt.append(counts(emb.backend.name, "reduce_scatter"))
d = emb._plan.data
out["auto"] = {
    "backend": emb.backend.name, "errs": errs, "compiles": compiles,
    "counts": cnt, "Z_shape": list(emb.Z_.shape),
    "labeled": [int((Y[g.u] >= 0).sum() + (Y[g.v] >= 0).sum()) for Y in Ys],
    "placed": {k: {"spec": str(d[k].sharding.spec),
                   "shape": list(d[k].shape),
                   "shards": sorted(s.data.shape[0]
                                    for s in d[k].addressable_shards),
                   "devices": len({s.device for s in d[k].addressable_shards})}
               for k in ("rows", "src", "w")},
    "edges_kept": sorted({"u", "v"} & set(d)),
    "spans": [e["name"] for e in obs.trace_events()]}


# each device's packed slots are the host packer's for its own share of
# the padded edges, padded with zero blocks to the fullest device's
def packed_as_host(d, tile_n, edge_block):
    gp = g.pad_to(20008)
    per, T, bpt = 20008 // p, d["rows"].shape[0] // p, d["rows"].shape[1]
    same, host_bpt = [], []
    for i in range(p):
        e = slice(i * per, (i + 1) * per)
        host = pack_edges(np.concatenate([gp.u[e], gp.v[e]]),
                          np.concatenate([gp.v[e], gp.u[e]]),
                          np.concatenate([gp.w[e], gp.w[e]]), 1004,
                          tile_n, edge_block)
        host_bpt.append(host[0].shape[1])
        for dev, ref in zip((d["rows"], d["src"], d["w"]), host[:3]):
            mine = np.asarray(dev)[i * T:(i + 1) * T]
            b = ref.shape[1]
            same.append(host[3] == T and b <= bpt and
                        bool(np.array_equal(mine[:, :b], ref)) and
                        not mine[:, b:].any())
    return {"same": same, "host_bpt": host_bpt, "bpt": bpt,
            "shape": list(d["rows"].shape)}


out["packing"] = {"256-512": packed_as_host(d, 256, 512)}
for tile_n, eb in ((64, 128), (32, 64)):
    e = Embedder(EncoderConfig(K=K, tile_n=tile_n, edge_block=eb),
                 backend="distributed:reduce_scatter",
                 plan_cache=None).fit(g, Ys[0])
    out["packing"][f"{tile_n}-{eb}"] = packed_as_host(e._plan.data,
                                                      tile_n, eb)

mesh = edge_mesh()
Zg = gee_distributed(g, Ys[-1], K=K, mesh=mesh)
out["auto"]["same_as_gee_distributed"] = bool(
    np.array_equal(emb.transform(), Zg))

# n and s divisible by the mesh: Z stays row-sharded, no slice
g2 = erdos_renyi(1000, 20000, seed=2, weighted=True)
emb2 = Embedder(EncoderConfig(K=K), plan_cache=None).fit(g2, Ys[0][:1000])
out["unpadded"] = {
    "spec": str(emb2.Z_.sharding.spec),
    "rows": sorted(s.data.shape[0] for s in emb2.Z_.addressable_shards),
    "err": float(np.abs(emb2.transform() - R.gee_numpy(
        g2.u, g2.v, g2.w, Ys[0][:1000], K, g2.n)).max())}

# reduce_scatter against the oracle: n = 1003 is a multiple of neither
# the kernel's 256-row tile nor p; donors 20%% labeled; nodes 900.. are
# isolated; 501 self-loops; the edge count pads to the mesh


def rs_err(g, Y, *, K=K, laplacian=False, functional=False, cfg=None):
    cfg = EncoderConfig(K=K, laplacian=laplacian, **(cfg or {}))
    Zref = R.gee_numpy(g.u, g.v, effective_weights(g, cfg), Y, K, g.n)
    if functional:
        Z = gee_distributed(g, Y, K=K, mesh=mesh, laplacian=laplacian)
    else:
        Z = Embedder(cfg, backend="distributed:reduce_scatter",
                     plan_cache=None).fit(g, Y).transform()
    # a class no node carries weighs 0, not 1/0: its column is 0
    absent = np.setdiff1d(np.arange(K), Y)
    return {"err": float(np.abs(Z - Zref).max()),
            "shape": list(Z.shape) == [g.n, K],
            "finite": bool(np.isfinite(Z).all()),
            "absent_zero": bool(np.all(Z[:, absent] == 0))}


rng = np.random.default_rng(3)
ends = rng.integers(0, 900, (2, 15000)).astype(np.int32)
loops = rng.integers(0, 900, 501).astype(np.int32)
uu, vv = np.concatenate([ends[0], loops]), np.concatenate([ends[1], loops])
unit = Graph(uu, vv, np.ones(15501, np.float32), 1003)
weighted = Graph(uu, vv, (rng.random(15501) + 0.5).astype(np.float32), 1003)
Yc = make_labels(1003, K, 0.2, rng)
out["rs_cases"] = {
    "unit": rs_err(unit, Yc),
    "weighted": rs_err(weighted, Yc),
    "laplacian": rs_err(weighted, Yc, laplacian=True),
    "functional": rs_err(weighted, Yc, functional=True),
    "functional_laplacian": rs_err(weighted, Yc, laplacian=True,
                                   functional=True)}
# the encoder conformance zoo at its small kernel geometry; and a graph
# with no edges, which pads to one zero-weight edge a device
for case, (gc, Yz) in _cases().items():
    out["rs_cases"][case] = rs_err(
        gc, Yz, K=int(Yz.max()) + 1 if Yz.max() >= 0 else 3, cfg=CFG)
empty = Graph(np.zeros(0, np.int32), np.zeros(0, np.int32),
              np.zeros(0, np.float32), 16)
out["rs_cases"]["empty_graph"] = rs_err(
    empty, make_labels(16, 3, 0.5, np.random.default_rng(1)), K=3, cfg=CFG)

# the backend by name through the Embedder: Z, and one embed's counters
name = "distributed:reduce_scatter"
before = counts(name, "reduce_scatter")
e = Embedder(EncoderConfig(K=K), backend=name, plan_cache=None)
e.fit(g, Ys[1])
out["reduce_scatter"] = {
    "err": float(np.abs(e.transform() - R.gee_numpy(
        g.u, g.v, g.w, Ys[1], K, g.n)).max()),
    "bpt": e._plan.data["rows"].shape[1],
    "delta": [a - b for a, b in zip(counts(name, "reduce_scatter"),
                                    before)]}
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def res():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={P_DEV}",
               PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c",
                        SCRIPT % {"K": K, "p": P_DEV,
                                  "tests": os.path.join(REPO, "tests")}],
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


S, N_PAD = 20007, 1004
#: the kernel's tiles over N_PAD rows, and its edge block
T, EB = 4, 512


def test_runs_on_4_devices(res):
    assert res["devices"] == P_DEV


def test_auto_resolves_to_reduce_scatter(res):
    assert res["auto"]["backend"] == "distributed:reduce_scatter"


@pytest.mark.parametrize("step", [0, 1, 2], ids=["fit", "refit", "refit2"])
def test_fit_and_refits_match_oracle(res, step):
    assert res["auto"]["errs"][step] < 1e-4
    assert res["auto"]["Z_shape"] == [1003, K]


@pytest.mark.parametrize("arr", ["rows", "src", "w"])
def test_plan_edges_sharded_a_quarter_per_device(res, arr):
    """The plan keeps each device's packed slots on that device, one
    (T, BPT, 1, EB) set a device, and releases the placed edges."""
    placed = res["auto"]["placed"][arr]
    assert placed["spec"] == "PartitionSpec('edges',)"
    assert placed["devices"] == P_DEV
    assert placed["shards"] == [T] * P_DEV
    assert placed["shape"][0] == P_DEV * T
    assert placed["shape"][2:] == [1, EB]
    assert res["auto"]["edges_kept"] == []


@pytest.mark.parametrize("geometry", ["256-512", "64-128", "32-64"])
def test_device_packing_equals_host_packing(res, geometry):
    """Each device's (rows, src, w) slots equal `kernels.ops.pack_edges`
    of its own contributions, slot for slot, padded with zero blocks to
    the fullest device's block count, at (tile_n, edge_block)
    `geometry`."""
    m = res["packing"][geometry]
    tile_n, eb = map(int, geometry.split("-"))
    assert m["same"] == [True] * (3 * P_DEV)
    assert m["bpt"] == max(m["host_bpt"])
    assert m["shape"] == [P_DEV * -(-N_PAD // tile_n), m["bpt"], 1, eb]


#: the encoder conformance zoo (`test_encoder._cases`) and a graph with
#: no edges, held to the conformance suite's tolerance
ZOO = ["all_unlabeled", "empty_class", "self_loops", "sparsely_labeled",
       "weighted_directed", "empty_graph"]


@pytest.mark.parametrize("case", ["unit", "weighted", "laplacian",
                                  "functional", "functional_laplacian"]
                         + ZOO)
def test_reduce_scatter_matches_oracle(res, case):
    """Self-loops, isolated nodes, unlabeled donors, n a multiple of
    neither the tile nor the mesh; through the Embedder and through
    `gee_distributed`, with and without Laplacian scaling; the encoder's
    conformance zoo at its small geometry, where a class no node carries
    gives a column of 0; and the empty graph, whose Z is all 0."""
    m = res["rs_cases"][case]
    assert m["err"] < (1e-5 if case in ZOO else 1e-4)
    assert m["shape"] and m["finite"] and m["absent_zero"]


def test_second_refit_compiles_nothing(res):
    assert res["auto"]["compiles"][2] == 0


@pytest.mark.parametrize("step", [0, 1, 2], ids=["fit", "refit", "refit2"])
def test_counters_by_hand(res, step):
    """Per embed: p T BPT EB packed slots, padding included; the 2 s
    real contributions split by the donor's label; (p-1)/p of the
    (n_pad, K) f32 accumulator sent."""
    labeled = sum(res["auto"]["labeled"][:step + 1])
    k = step + 1
    bpt = res["auto"]["placed"]["rows"]["shape"][1]
    assert res["auto"]["counts"][step] == [
        k * P_DEV * T * bpt * EB, labeled, k * 2 * S - labeled,
        k * (P_DEV - 1) * N_PAD * K * 4 // P_DEV]


def test_spans(res):
    spans = res["auto"]["spans"]
    assert spans.count("encoder.place") == 1          # one plan
    assert spans.count("encoder.shard_embed") == 3    # fit, two refits


def test_class_weights_give_make_w_z(res):
    """The class weights scale Z's columns the same way through the
    Embedder and through `gee_distributed`: the same Z, bit for bit."""
    assert res["auto"]["same_as_gee_distributed"]


def test_unpadded_z_stays_row_sharded(res):
    u = res["unpadded"]
    assert u["spec"] == "PartitionSpec('edges',)"
    assert u["rows"] == [1000 // P_DEV] * P_DEV
    assert u["err"] < 1e-4


@pytest.mark.parametrize("mode", ["reduce_scatter"])
def test_mode_counts_by_hand(res, mode):
    """The backend chosen by name: one embed's p T BPT EB slots, its
    2 s contributions and (p-1)/p of the (n_pad, K) accumulator sent."""
    m = res[mode]
    assert m["err"] < 1e-4
    assert m["bpt"] == max(res["packing"]["256-512"]["host_bpt"])
    assert m["delta"][0] == P_DEV * T * m["bpt"] * EB
    assert m["delta"][1] + m["delta"][2] == 2 * S
    assert m["delta"][3] == (P_DEV - 1) * N_PAD * K * 4 // P_DEV
