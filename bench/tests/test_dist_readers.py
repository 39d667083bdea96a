"""The readers of the four-chip cell's metrics on a hand-made reduced
trace of four devices and hand-made registry deltas, each answer worked
out by hand: its own (`collective_ms.dist`, `collective_roofline.dist`)
and those it shares with `er-batch.refit` (`device_idle.batch`,
`slot_fill`, `useful_fill`, `window_compiles`)."""
import pytest

from yardstick import ici
from yardstick.cells import Cell
from yardstick.peaks import PEAKS
from yardstick.registry import Delta

V5E = PEAKS["TPU v5 lite"]
BYTES = "repro_distributed_collective_bytes_total"
SLOTS = "repro_kernel_slots_total"
CONTRIB = "repro_kernel_contributions_total"
COMPILES = "repro_jax_compiles_total"
RS = "distributed:reduce_scatter"


def _dev(offset):
    """One device's two steps in a 1000 ns window: compute, then a
    synchronous all-reduce of 40 ns, then compute, then an async
    all-reduce pair from 600 + offset to 660 + offset (60 ns)."""
    return [
        ["%fusion.4 = f32[81750000]{0} fusion(...)", 100, 300],
        ["%all-reduce = f32[3000000,50]{0,1:T(8,128)} all-reduce(...)",
         400, 40],
        ["%scatter.2 = f32[150000000]{0} scatter(...)", 500, 100],
        ["%all-reduce-start.1 = f32[8]{0} all-reduce-start(...)",
         600 + offset, 10],
        ["%fusion.7 = f32[8]{0} fusion(...)", 610 + offset, 20],
        ["%all-reduce-done.1 = f32[8]{0} all-reduce-done(...)",
         650 + offset, 10]]


HAND = {"window": [0, 1000],
        "ops": {f"/device:TPU:{i}": _dev(10 * i) for i in range(4)},
        "modules": {}, "host": []}


def _snap(**counters):
    return {"counters": counters, "gauges": {}, "histograms": {}}


class Ctx:
    def __init__(self, trace=HAND, before=None, after=None, steps=2,
                 backend=RS, peaks=V5E):
        self.trace = trace
        self.delta = Delta(before or _snap(), after or _snap())
        self.records = {"steps": steps, "backend": backend}
        self.peaks = peaks


def _read(metric, ctx):
    return Cell.reader(metric).read(ctx)


def test_intervals_pair_start_and_done():
    per, closed = ici.collective_intervals(HAND)
    assert closed and len(per) == 4
    assert per["/device:TPU:1"] == [(400, 440), (610, 670)]
    # 40 + 60 ns on each of the four devices
    assert ici.collective_s_per_chip(HAND) == (pytest.approx(100e-9), True)


def test_collective_ms_by_hand():
    # 100 ns a chip over 2 steps
    assert _read("collective_ms.dist", Ctx()) == pytest.approx(5e-5)
    assert _read("collective_ms.dist", Ctx(steps=0)) is None
    quiet = dict(HAND, ops={"/device:TPU:0": [
        ["%fusion.4 = f32[8]{0} fusion(...)", 100, 300]]})
    assert _read("collective_ms.dist", Ctx(trace=quiet)) is None


def test_collective_roofline_by_hand():
    # 2 embeds x 9,000 B a chip in 100 ns a chip, against 200 GB/s
    ctx = Ctx(before=_snap(**{f'{BYTES}{{mode="reduce_scatter"}}': 4e3}),
              after=_snap(**{f'{BYTES}{{mode="reduce_scatter"}}': 22e3}))
    assert ici.ici_bw(V5E) == pytest.approx(200e9)
    assert _read("collective_roofline.dist", ctx) == \
        pytest.approx(100.0 * 18e3 / 100e-9 / 200e9)


def test_collective_roofline_silent():
    """No reading without counted bytes, off the chip, or where a
    collective's start has no done in the window."""
    after = _snap(**{f'{BYTES}{{mode="reduce_scatter"}}': 1e3})
    assert _read("collective_roofline.dist", Ctx()) is None
    assert _read("collective_roofline.dist",
                 Ctx(after=after, peaks=None)) is None
    cut = dict(HAND, window=[0, 655])      # TPU:1..3's done falls outside
    per, closed = ici.collective_intervals(cut)
    assert not closed
    assert _read("collective_roofline.dist",
                 Ctx(trace=cut, after=after)) is None


def test_device_idle_by_hand():
    # busy a device: [100, 440) + [500, 600) + [600+o, 630+o)
    # + [650+o, 660+o) = 480 of 1000 ns (the async pair's gap is idle),
    # the same on each of the four devices
    assert _read("device_idle.batch", Ctx()) == pytest.approx(52.0)
    empty = dict(HAND, ops={})
    assert _read("device_idle.batch", Ctx(trace=empty)) is None


#: 2 embeds of 654M slots, 65.4M labeled contributions each
FILL_BEFORE = _snap(**{
    f'{SLOTS}{{backend="{RS}"}}': 654e6,
    f'{CONTRIB}{{backend="{RS}",donor="labeled"}}': 65e6,
    f'{CONTRIB}{{backend="{RS}",donor="unlabeled"}}': 589e6})
FILL_AFTER = _snap(**{
    f'{SLOTS}{{backend="{RS}"}}': 3 * 654e6,
    f'{CONTRIB}{{backend="{RS}",donor="labeled"}}': 65e6 + 130.8e6,
    f'{CONTRIB}{{backend="{RS}",donor="unlabeled"}}': 589e6 + 1177.2e6})


def test_useful_fill_by_hand():
    assert _read("useful_fill", Ctx(before=FILL_BEFORE, after=FILL_AFTER)) \
        == pytest.approx(10.0)
    assert _read("useful_fill", Ctx()) is None


def test_slot_fill_by_hand():
    # every slot of the unpadded edge list holds a contribution
    assert _read("slot_fill", Ctx(before=FILL_BEFORE, after=FILL_AFTER)) \
        == pytest.approx(100.0)
    assert _read("slot_fill", Ctx()) is None


def test_window_compiles_by_hand():
    assert _read("window_compiles",
                 Ctx(before=_snap(**{COMPILES: 40.0}),
                     after=_snap(**{COMPILES: 40.0}))) == 0
    assert _read("window_compiles",
                 Ctx(before=_snap(**{COMPILES: 40.0}),
                     after=_snap(**{COMPILES: 130.0}))) == \
        pytest.approx(90.0)
    assert _read("window_compiles", Ctx()) is None
