"""The roofline work counts depend on n, s and K only: a packing with another tile size, edge block or padding leaves
them unchanged."""
import inspect

import numpy as np
import pytest

from yardstick import counts
from yardstick.peaks import PEAKS, peaks


@pytest.mark.parametrize("tile_n,edge_block", [(256, 512), (128, 256),
                                               (512, 1024), (256, 128)])
def test_scatter_work_ignores_layout(tile_n, edge_block):
    from repro.kernels.ops import pack_edges
    rng = np.random.default_rng(0)
    n, s, K = 3000, 20000, 50
    dst = rng.integers(0, n, 2 * s)
    rows, _, _, T = pack_edges(dst, np.zeros_like(dst),
                               np.ones(2 * s, np.float32), n, tile_n,
                               edge_block)
    padded = rows.size - 2 * s        # what this layout pads
    assert padded >= 0
    assert counts.scatter_work(n, s, K) == (12.0 * s + 8.0 * n
                                            + 4.0 * n * K, 4.0 * s)


def test_count_signatures_hold_no_layout():
    layout = {"tile_n", "edge_block", "bpt", "T", "padding", "bucket"}
    assert not layout & set(inspect.signature(counts.scatter_work)
                            .parameters)


def test_roofline_share_is_the_larger_bound():
    pk = peaks("TPU v5 lite")
    assert counts.roofline_share(819e9, 0.0, 1.0, pk) == pytest.approx(100)
    assert counts.roofline_share(0.0, 197e12, 2.0, pk) == pytest.approx(50)
    assert counts.roofline_share(1.0, 1.0, 0.0, pk) is None


def test_unknown_device_has_no_peaks():
    assert "TPU v5 lite" in PEAKS
    with pytest.raises(KeyError):
        peaks("cpu")
