"""With the timed path broken underneath, a run's `correct` comes out
false: once for each fault the cell can have.  (Every cell runs on one
chip, so there is no exchange between chips to leave out.)  The fault is
planted at the window's start, after a sound set-up, on whichever
backend the Embedder resolved: each case runs once as `auto` resolves
on the CPU (xla) and once with `auto` sent to the pallas kernel (in
interpret mode), the path a cell times on the chip."""
import copy

import pytest

from conftest import cell_names, run_tiny
from yardstick.cells import Cell


def _broken_window(monkeypatch, cell, plant):
    """Make the cell's driver plant `plant(monkeypatch)` as its window
    starts."""
    drv = Cell(cell).driver().Driver
    orig = drv.window

    def window(self):
        plant(monkeypatch)
        return orig(self)
    monkeypatch.setattr(drv, "window", window)


def _on_backend(mp, wrap):
    """Route every embed through `wrap(embed, plan, Yj, Wv)`, where
    `embed` is the resolved backend's own."""
    from repro.encoder.embedder import Embedder
    orig = Embedder._embed

    def _embed(self, plan, Y):
        inner = self.backend.embed
        self.backend.embed = lambda p, Yj, Wv: wrap(inner, p, Yj, Wv)
        try:
            return orig(self, plan, Y)
        finally:
            del self.backend.embed
    mp.setattr(Embedder, "_embed", _embed)


def _unchanged(mp):
    """A step returns its state unchanged."""
    from repro.encoder.embedder import Embedder
    mp.setattr(Embedder, "_embed", lambda self, plan, Y: self)


def _half_edges(mp):
    """Half of the edge weights (xla: the second half of the edges;
    pallas: the packed blocks of the second half of the row tiles) left
    out."""
    def wrap(embed, plan, Yj, Wv):
        p = copy.copy(plan)
        p.data = dict(plan.data)
        w = p.data["w"]
        p.data["w"] = w.at[w.shape[0] // 2:].set(0.0)
        return embed(p, Yj, Wv)
    _on_backend(mp, wrap)


def _altered(mp):
    """One answer altered where the backend produces it."""
    def wrap(embed, plan, Yj, Wv):
        Z, info = embed(plan, Yj, Wv)
        return Z.at[7, 3].add(1e-3), info
    _on_backend(mp, wrap)


@pytest.fixture(params=["auto", "pallas"])
def backend(request, monkeypatch):
    """`auto` as it resolves here, or `auto` resolved to pallas."""
    if request.param == "pallas":
        from repro.encoder import backends
        monkeypatch.setattr(backends, "AUTO_POLICY",
                            [("bench_test", lambda *a: "pallas")])
    return request.param


@pytest.mark.parametrize("cell", cell_names("batch"))
@pytest.mark.parametrize("fault", [_unchanged, _half_edges, _altered])
def test_batch_fault_is_caught(monkeypatch, backend, cell, fault):
    _broken_window(monkeypatch, cell, fault)
    rc, res, err = run_tiny(cell)
    assert rc == 0 and res["correct"] is False, res["compared"]


@pytest.mark.parametrize("cell", cell_names("batch"))
def test_sound_run_is_correct(monkeypatch, backend, cell):
    """The same runs without a fault are correct, on the backend the
    fault was planted on."""
    rc, res, err = run_tiny(cell)
    assert rc == 0 and res["correct"] is True, res["compared"]
