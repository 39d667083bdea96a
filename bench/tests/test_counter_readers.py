"""The readers of the program's own counters (`slot_fill`, `useful_fill`,
`window_compiles`) on hand-made registry deltas, and in a traced CPU
rehearsal of each cell that lists them."""
import pytest

from conftest import run_tiny
from yardstick.cells import Cell
from yardstick.registry import Delta

SLOTS = "repro_kernel_slots_total"
CONTRIB = "repro_kernel_contributions_total"
COMPILES = "repro_jax_compiles_total"
READERS = ("slot_fill", "useful_fill", "window_compiles")


class Ctx:
    def __init__(self, before, after):
        self.delta = Delta(before, after)


def _read(metric, before, after):
    return Cell.reader(metric).read(Ctx(before, after))


def _snap(slots, labeled, unlabeled, compiles=None):
    c = {f'{SLOTS}{{backend="pallas"}}': slots,
         f'{CONTRIB}{{backend="pallas",donor="labeled"}}': labeled,
         f'{CONTRIB}{{backend="pallas",donor="unlabeled"}}': unlabeled}
    if compiles is not None:
        c[COMPILES] = compiles
    return {"counters": c, "gauges": {}, "histograms": {}}


def test_fills_by_hand():
    # a window of two embeds: 2 x 64,000,000 slots holding 2 x 60M
    # contributions, 6.5M of each embed's from labeled donors
    before = _snap(64e6, 6.6e6, 53.4e6)
    after = _snap(3 * 64e6, 6.6e6 + 13e6, 53.4e6 + 107e6)
    assert _read("slot_fill", before, after) == pytest.approx(93.75)
    assert _read("useful_fill", before, after) == \
        pytest.approx(100 * 13e6 / 128e6)


def test_fills_silent_without_slots():
    """No slot counted in the window (the program off, or without the
    counters): no reading, not a division by zero."""
    empty = {"counters": {}, "gauges": {}, "histograms": {}}
    for m in ("slot_fill", "useful_fill"):
        assert _read(m, empty, empty) is None
        assert _read(m, _snap(5, 1, 3), _snap(5, 1, 3)) is None


def test_window_compiles():
    assert _read("window_compiles", _snap(0, 0, 0, 40.0),
                 _snap(0, 0, 0, 40.0)) == 0
    assert _read("window_compiles", _snap(0, 0, 0, 40.0),
                 _snap(0, 0, 0, 43.0)) == pytest.approx(3.0)
    # the series first appears inside the window
    assert _read("window_compiles", _snap(0, 0, 0),
                 _snap(0, 0, 0, 2.0)) == pytest.approx(2.0)
    # a program that counts no compiles gives no reading
    assert _read("window_compiles", _snap(0, 0, 0), _snap(0, 0, 0)) is None


def test_traced_rehearsal_reports_counters(cells):
    """A traced CPU rehearsal of each cell that a counter metric lists
    reports that metric: fills are shares of the slots, and a warmed-up
    window compiles nothing."""
    for name in cells:
        listed = [m["name"] for m in Cell(name).per_layer
                  if m["name"] in READERS]
        if not listed:
            continue
        rc, res, err = run_tiny(name, trace=True)
        assert rc == 0, err
        m = {k: v["value"] for k, v in res["metrics"].items()}
        print(name, {k: m.get(k) for k in listed})
        assert set(listed) <= set(m)
        if "slot_fill" in m:
            assert 0 < m["slot_fill"] <= 100
        if "useful_fill" in m:
            assert 0 < m["useful_fill"] <= m.get("slot_fill", 100)
        if "window_compiles" in m:
            assert m["window_compiles"] == 0
