"""Every cell in BENCHMARK.json loads through the harness, and the file
keeps to the benchmark's contract."""
import json
import os
import re

import pytest

from yardstick.cells import BENCH, ROOT, Cell

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _bm():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys_and_names():
    bm = _bm()
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert bm["paths"] == ["bench"] and bm["command"][1] == "bench/run.py"
    assert 1 <= bm["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bm[k]]
    assert all(NAME.match(n) for n in names)
    assert len({x["name"] for x in bm["workloads"]}) == len(bm["workloads"])
    metrics = [m["name"] for m in bm["end_to_end"] + bm["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    setup = [m for m in bm["end_to_end"] if m["name"] == "setup_s"]
    assert setup and setup[0]["bound"] <= 0.25
    for m in bm["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert len(json.dumps(bm)) < 64 * 1024


def test_pairs_and_moves():
    bm = _bm()
    pairs = [(w["config"], w["traffic"]) for w in bm["workloads"]]
    assert len(set(pairs)) == len(pairs)
    cells = {w["name"] for w in bm["workloads"]}
    e2e = {m["name"]: set(m.get("workloads", cells))
           for m in bm["end_to_end"]}
    for m in bm["per_layer"]:
        assert m["moves"] in e2e
        for w in m["workloads"]:
            assert w in cells and w in e2e[m["moves"]]
    for w in cells:
        assert w in e2e["setup_s"]
        assert any(w in ws for n, ws in e2e.items() if n != "setup_s")
        assert any(w in m["workloads"] for m in bm["per_layer"])


@pytest.mark.parametrize("name", [w["name"] for w in _bm()["workloads"]])
def test_cell_loads(name):
    cell = Cell(name)
    assert cell.limits and cell.end_to_end and cell.per_layer
    assert hasattr(cell.driver(), "Driver")
    for m in cell.per_layer:
        assert callable(Cell.reader(m["name"]).read)
    cfg = [c for c in _bm()["configs"] if c["name"] == cell.entry["config"]]
    assert cfg[0]["file"].startswith("bench/configs/")
    assert os.path.exists(os.path.join(BENCH, "workloads", name + ".json"))


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        Cell("no-such-cell")
