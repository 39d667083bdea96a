"""Window deltas of the program's counters and histograms, which a
per-layer reader gets as `ctx.delta`, from two registry snapshots."""
import pytest

from yardstick.registry import Delta

BEFORE = {
    "counters": {'repro_x_total{shard="0"}': 3.0},
    "histograms": {'repro_y_seconds{kind="a"}': {"count": 2, "sum": 0.5}},
}
AFTER = {
    "counters": {'repro_x_total{shard="0"}': 7.0,
                 'repro_x_total{shard="1"}': 2.0},
    "histograms": {'repro_y_seconds{kind="a"}': {"count": 5, "sum": 1.1},
                   'repro_y_seconds{kind="b"}': {"count": 1, "sum": 4.0}},
}


def test_counter_delta_sums_matching_series():
    d = Delta(BEFORE, AFTER)
    assert d.counter("repro_x_total") == pytest.approx(6.0)
    assert d.counter("repro_x_total", shard="1") == pytest.approx(2.0)
    assert d.counter("repro_absent_total") == 0


def test_histogram_delta_and_mean():
    d = Delta(BEFORE, AFTER)
    assert d.hist("repro_y_seconds", kind="a") == (3, pytest.approx(0.6))
    assert d.hist("repro_y_seconds") == (4, pytest.approx(4.6))
    assert d.hist_mean("repro_y_seconds", kind="a") == pytest.approx(0.2)
    assert d.hist_mean("repro_y_seconds", kind="c") is None


def test_real_registry_snapshots():
    """The program's own registry snapshots have the shape read here."""
    from repro import obs
    assert obs.enabled()            # the cells run at the default, on
    before = obs.registry().snapshot()
    obs.counter("repro_benchtest_events_total", 2.0, kind="t")
    after = obs.registry().snapshot()
    assert Delta(before, after).counter("repro_benchtest_events_total",
                                        kind="t") == pytest.approx(2.0)
