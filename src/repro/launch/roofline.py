"""Roofline-term derivation from compiled dry-run artifacts.

Conventions (documented once, used everywhere):
  * ``cost_analysis()`` on an SPMD executable reports PER-DEVICE flops
    and bytes (verified empirically in this repo), so
        compute_term_s = flops / peak FLOP/s
        memory_term_s  = bytes / peak HBM bytes/s
    need no further division by chip count.
  * collective bytes are parsed from the compiled HLO: for every
    all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute op we take the RESULT shape's bytes (the
    per-device view).  All-reduce is weighted 2x (ring send+recv);
    others 1x.  This is a structural lower bound — it ignores the
    (P-1)/P factors and latency terms, which is fine for a
    dominant-term comparison.
  * MODEL_FLOPS = 6*N*D for training (fwd+bwd), 2*N*D for fwd-only
    (prefill/decode), with D = global tokens in the step and N the
    (active) parameter count.  The ratio MODEL_FLOPS / (flops * chips)
    measures how much compiled compute is "useful".

Hardware model: the published per-chip peaks in `PEAKS`, keyed by
``jax.Device.device_kind``.  The dry run models a v5e (`DRYRUN_KIND`).
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Dict


@dataclass(frozen=True)
class Peaks:
    flops: float          # bf16 FLOP/s
    hbm_bw: float         # HBM bytes/s
    ici_bw: float         # chip-to-chip interconnect bytes/s
    hbm_bytes: float      # HBM capacity


#: Published peaks of one chip, by `device_kind`.  A device that is not
#: here has no roofline: `peaks` raises rather than assume one.
PEAKS: Dict[str, Peaks] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB
    # of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, ici_bw=1600e9 / 8,
                         hbm_bytes=16e9),
}

#: the chip the dry-run roofline models
DRYRUN_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> Peaks:
    """The peaks of `device_kind` (``jax.devices()[0].device_kind``)."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLL_KINDS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")
# matches e.g. "%all-reduce.5 = f32[16,128]{1,0} all-reduce("
_OP_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_WIRE_WEIGHT = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
                "all-to-all": 1.0, "collective-permute": 1.0}


def shape_bytes(shape_str: str) -> int:
    """'f32[16,128]{1,0}' or '(f32[2], bf16[4,4])' -> total bytes."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


_GROUP_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUP_LIST_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")


def parse_collectives(hlo_text: str) -> Dict[str, dict]:
    """Per collective kind: {'count', 'bytes', 'wire_bytes'} (per-device).

    reduce-scatter's RESULT is the scattered shard (input/P), so its wire
    cost is result_bytes x (group_size - 1) — the group size is parsed
    from the op's replica_groups attribute (iota form [G,N]<=[...])."""
    out = {k: {"count": 0, "bytes": 0, "wire_bytes": 0.0}
           for k in _COLL_KINDS}
    lines = hlo_text.splitlines()
    for line in lines:
        m = _OP_RE.search(line)
        if not m:
            continue
        shape_str, kind = m.group(1), m.group(2)
        b = shape_bytes(shape_str)
        w = b * _WIRE_WEIGHT[kind]
        if kind == "reduce-scatter":
            g = _GROUP_RE.search(line)
            if g:
                gsize = int(g.group(2))
            else:
                gl = _GROUP_LIST_RE.search(line)
                gsize = len(gl.group(1).split(",")) if gl else 2
            w = b * max(gsize - 1, 1)
        out[kind]["count"] += 1
        out[kind]["bytes"] += b
        out[kind]["wire_bytes"] += w
    return out


@dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_device: float
    bytes_per_device: float
    collective_bytes: float          # per-device wire bytes
    collectives: dict
    model_flops_global: float
    arg_bytes: int = 0
    temp_bytes: int = 0
    out_bytes: int = 0

    @property
    def compute_s(self) -> float:
        return self.flops_per_device / peaks(DRYRUN_KIND).flops

    @property
    def memory_s(self) -> float:
        return self.bytes_per_device / peaks(DRYRUN_KIND).hbm_bw

    @property
    def collective_s(self) -> float:
        return self.collective_bytes / peaks(DRYRUN_KIND).ici_bw

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_s(self) -> float:
        """Optimistic (perfect-overlap) step time = max of terms."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self) -> float:
        tot = self.flops_per_device * self.chips
        return self.model_flops_global / tot if tot else 0.0

    @property
    def mfu(self) -> float:
        """MODEL_FLOPS / (step_s * chips * peak) — roofline fraction."""
        denom = self.step_s * self.chips * peaks(DRYRUN_KIND).flops
        return self.model_flops_global / denom if denom else 0.0

    @property
    def hbm_fit(self) -> bool:
        return (self.arg_bytes + self.temp_bytes
                <= peaks(DRYRUN_KIND).hbm_bytes)

    def to_dict(self) -> dict:
        d = {k: getattr(self, k) for k in (
            "arch", "shape", "mesh", "chips", "flops_per_device",
            "bytes_per_device", "collective_bytes", "model_flops_global",
            "arg_bytes", "temp_bytes", "out_bytes")}
        d["collectives"] = self.collectives
        for k in ("compute_s", "memory_s", "collective_s", "dominant",
                  "step_s", "useful_flops_ratio", "mfu", "hbm_fit"):
            d[k] = getattr(self, k)
        return d


def model_flops(cfg, shape) -> float:
    """6*N*D train / 2*N*D fwd-only, N = active params."""
    from repro.models.model import count_params_analytic
    n = count_params_analytic(cfg, active_only=True)
    if shape.kind == "train":
        return 6.0 * n * shape.tokens
    if shape.kind == "prefill":
        return 2.0 * n * shape.tokens
    return 2.0 * n * shape.global_batch        # decode: one token per seq


def build(arch: str, shape, mesh_name: str, chips: int, compiled,
          cfg=None) -> Roofline:
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    colls = parse_collectives(compiled.as_text())
    wire = sum(c["wire_bytes"] for c in colls.values())
    ma = compiled.memory_analysis()
    mf = model_flops(cfg, shape) if cfg is not None else 0.0
    return Roofline(
        arch=arch, shape=shape.name if hasattr(shape, "name") else shape,
        mesh=mesh_name, chips=chips,
        flops_per_device=float(ca.get("flops", 0.0)),
        bytes_per_device=float(ca.get("bytes accessed", 0.0)),
        collective_bytes=wire, collectives=colls,
        model_flops_global=mf,
        arg_bytes=getattr(ma, "argument_size_in_bytes", 0),
        temp_bytes=getattr(ma, "temp_size_in_bytes", 0),
        out_bytes=getattr(ma, "output_size_in_bytes", 0))
