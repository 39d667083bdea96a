"""Collectives on the chip-to-chip interconnect (ICI): the published
bandwidth of one chip, by `jax.Device.device_kind`, and the collective
ops' device intervals in a reduced trace (`yardstick/trace.py`).

A collective op is found by its HLO instruction name, as the chip's
trace shows it (`%all-reduce = f32[3000000,50]{0,1:T(8,128)}
all-reduce(...)` on v5e, where XLA lowers `psum_scatter` of the (n, K)
accumulator to an all-reduce and a slice): `reduce-scatter`,
`all-reduce`, `all-gather`, `collective-permute`, `all-to-all`, each
synchronous, or split into `<name>-start` and `<name>-done`, timed from
the start op's start to the done op's end.
"""
from __future__ import annotations

from yardstick import trace
from yardstick.peaks import PEAKS

#: Google Cloud documentation, "TPU v5e": 1,600 Gbit/s of interchip
#: interconnect bandwidth a chip
ICI_BW = {"TPU v5 lite": 1600e9 / 8}

COLLECTIVES = ("reduce-scatter", "all-reduce", "all-gather",
               "collective-permute", "all-to-all")


def ici_bw(pk):
    """ICI bytes/s of the chip whose published peaks are `pk` (a reader
    is handed the peaks, not the kind); None where none is published."""
    for kind, p in PEAKS.items():
        if p == pk:
            return ICI_BW.get(kind)
    return None


def collective_intervals(tr):
    """({device: [(start, end), ...]}, closed): every collective in the
    window on each device that ran one; closed is False where a `-start`
    op has no `-done` after it in the window."""
    t0, t1 = tr["window"]
    out, closed = {}, True
    for dev, evs in tr["ops"].items():
        spans, open_ = [], {}
        for name, a, b in sorted(trace._clip(evs, t0, t1),
                                 key=lambda e: e[1]):
            op = trace.instr_name(name)
            if op in COLLECTIVES:
                spans.append((a, b))
            elif op.endswith("-start") and op[:-6] in COLLECTIVES:
                open_.setdefault(op[:-6], []).append(a)
            elif op.endswith("-done") and op[:-5] in COLLECTIVES:
                starts = open_.get(op[:-5])
                if starts:
                    spans.append((starts.pop(0), b))
        if any(open_.values()):
            closed = False
        if spans:
            out[dev] = spans
    return out, closed


def collective_s_per_chip(tr):
    """(seconds of collectives per chip, averaged over the devices that
    ran any, closed), or (0.0, closed) where none ran."""
    per, closed = collective_intervals(tr)
    if not per:
        return 0.0, closed
    total = sum(b - a for spans in per.values() for a, b in spans)
    return total / len(per) / 1e9, closed
