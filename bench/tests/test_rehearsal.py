"""A tiny CPU rehearsal of each cell runs through the harness to its
last line: a result with every key, `correct` true, the cell's
end-to-end metrics (untraced) or a breakdown (traced)."""
import pytest

from conftest import run_tiny


@pytest.mark.parametrize("trace", [False, True])
def test_rehearsal(cells, trace):
    from yardstick.cells import Cell
    for name in cells:
        rc, res, err = run_tiny(name, trace=trace)
        assert rc == 0, err
        assert set(res) >= {"correct", "attempted", "failed", "metrics",
                            "device", "compared"}
        assert list(res)[-1] == "compared"
        assert res["correct"] is True, res["compared"]
        assert res["failed"] == 0 and res["attempted"] > 0
        cell = Cell(name)
        if trace:
            assert {"busy_s", "window_s"} <= set(res["device"])
            assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
        else:
            assert set(res["metrics"]) == {m["name"]
                                           for m in cell.end_to_end}
        assert err.strip().splitlines()[-1].startswith("compared ")
