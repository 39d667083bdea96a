"""The trace reduction (busy union, idle share, kernel time by name,
breakdown) gives fixed numbers: on a hand-made trace whose answer is
worked out by hand, and on a small trace recorded on a v5e."""
import json
import os

import pytest

from yardstick import trace

HERE = os.path.dirname(os.path.abspath(__file__))

HAND = {
    "window": [0, 100],
    "ops": {"/device:TPU:0": [
        ["%fusion.3 = f32[8]{0} fusion(...)", 10, 20],
        ["%copy.1 = f32[8]{0} copy(...)", 20, 20],
        ["%gee_scatter_pallas.1 = f32[256,56]{1,0} custom-call(...)", 50, 20],
        ["%fusion.3 = f32[8]{0} fusion(...)", 95, 15],
        ["%fusion.9 = f32[8]{0} fusion(...)", 120, 5]]},
    "modules": {"/device:TPU:0": [["jit_gather(123)", 10, 30],
                                  ["jit_gee_scatter_pallas(9)", 50, 20],
                                  ["jit_gather(123)", 95, 15]]},
    "host": [["bench.step", 5, 70], ["bench.flush", 40, 10]],
}


def test_hand_trace():
    assert trace.window_s(HAND) == pytest.approx(100e-9)
    # [10, 40) + [50, 70) + [95, 100): overlap merged, tail clipped
    assert trace.busy_s(HAND) == pytest.approx(55e-9)
    assert trace.idle_share(HAND) == pytest.approx(0.45)
    assert trace.kernel_s(HAND, "gee_scatter_pallas") == pytest.approx(20e-9)
    assert trace.kernel_events(HAND, "gee_scatter_pallas") == [(50, 70)]
    assert trace.ops_s(HAND) == pytest.approx(65e-9)
    bd = trace.breakdown(HAND)
    assert bd["device_ops"][0] == ["jit_gather:fusion", pytest.approx(25e-9)]
    assert dict(map(tuple, bd["idle_gaps"])) == {
        "bench.step": pytest.approx(35e-9),     # [0, 10) and [70, 95)
        "bench.flush": pytest.approx(10e-9)}    # [40, 50): the innermost


def test_instr_name():
    assert trace.instr_name("%topk_fused.1 = (f32[256,10]) custom-call(x)") \
        == "topk_fused"
    assert trace.instr_name("%gee_delta_renorm = (f32[8]) custom-call(y)") \
        == "gee_delta_renorm"
    assert trace.instr_name("%copy-done = f32[8] copy-done(z)") == "copy-done"


def _sweep_busy(tr):
    """Busy time by a boundary sweep: an independent count of the union."""
    t0, t1 = tr["window"]
    edges = []
    for evs in tr["ops"].values():
        for _, s, d in evs:
            a, b = max(s, t0), min(s + d, t1)
            if b > a:
                edges += [(a, 1), (b, -1)]
    busy = depth = 0
    last = None
    for x, step in sorted(edges):
        if depth > 0:
            busy += x - last
        depth += step
        last = x
    return busy / 1e9


def test_recorded_v5e_trace():
    """Two refits of a 20k-node SBM and one served batch (a top-k, an
    embed, a predict and a 256-edge insert), traced on one v5e."""
    with open(os.path.join(HERE, "data", "v5e_trace.json")) as f:
        tr = json.load(f)
    assert trace.window_s(tr) == pytest.approx(0.085665167, abs=1e-12)
    assert trace.busy_s(tr) == pytest.approx(0.032894488, abs=1e-12)
    assert trace.busy_s(tr) == pytest.approx(_sweep_busy(tr), abs=1e-12)
    assert trace.idle_share(tr) == pytest.approx(0.6160109277555019)
    assert trace.kernel_s(tr, "gee_scatter_pallas") == \
        pytest.approx(0.003688911, abs=1e-12)
    assert len(trace.kernel_events(tr, "gee_scatter_pallas")) == 2
    assert trace.kernel_s(tr, "topk_fused") == \
        pytest.approx(0.000308658, abs=1e-12)
    assert trace.kernel_s(tr, "gee_delta_renorm") == \
        pytest.approx(0.000104568, abs=1e-12)
    bd = trace.breakdown(tr, top=3)
    assert bd["device_ops"] == [
        ["jit_gather:fusion", pytest.approx(0.02777693, abs=1e-12)],
        ["jit_gee_scatter_pallas:gee_scatter_pallas",
         pytest.approx(0.003688911, abs=1e-12)],
        ["jit_scatter-add:fusion", pytest.approx(0.000354393, abs=1e-12)]]
    assert bd["idle_gaps"] == [
        ["bench.step", pytest.approx(0.031486025, abs=1e-12)],
        ["bench.flush", pytest.approx(0.021284654, abs=1e-12)]]
