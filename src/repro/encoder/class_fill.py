"""The class fill: each packed slot's class, written from the labeled
donors only.

The pallas kernel reads one class per packed slot: the label of the
slot's donor, or -1, which matches no column of its one-hot.  Gathering
Y at every slot pays per index for all of them, though only the slots
of labeled donors carry a class: at GEE's usual 10% labeled, one slot in
ten.  A `DonorIndex` lists each donor's slots once per plan, in rows of
a fixed width, so an embed writes the labeled donors' classes into a
buffer of -1 and touches no other slot.  The slot layout does not depend
on labels, so no refit rebuilds the index.

`PallasBackend.embed` takes the fill while its writes, at
`FILL_WRITE_COST` gathered indices each, cost less than gathering every
slot; where nearly every node is labeled (refinement rounds) it gathers.
Both routes give the kernel the same class on every real slot and a
class that adds nothing on every padded one (w = 0 there), so Z is the
same bit for bit.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

#: what one write of the fill costs, in indices of the per-slot gather.
#: Measured on a TPU v5e at 1.6M nodes, 64.0M slots: the gather took
#: 549 ms (8.58 ns an index); the fill, 8.7M writes (181K rows of 48,
#: capacity padding, row gather, -1 buffer and XLA's sort and scatter
#: included), 90.6 ms (10.4 ns a write).
FILL_WRITE_COST = 1.2

#: what one row of the index costs beyond its lanes, in written lanes:
#: the host reads every row's donor label and the device gathers each
#: selected row's positions.  Sets the row width (`row_width`): 48 for
#: the Binomial degrees of a uniform graph of mean degree 37.5.
ROW_LANES = 16


def row_width(counts: np.ndarray) -> int:
    """The lanes W of an index row, from the donors' contribution counts:
    a donor with d contributions takes ceil(d / W) rows, and W (a
    multiple of 8, at most 256) minimizes rows * (W + ROW_LANES)."""
    hist = np.bincount(counts)
    d = np.arange(hist.size)
    best = None
    for w in range(8, min(256, max(8, hist.size - 1)) + 8, 8):
        cost = int(hist @ -(-d // w)) * (w + ROW_LANES)
        if best is None or cost < best[0]:
            best = (cost, w)
    return best[1]


def _bucket(x: int) -> int:
    """x rounded up to a sixteenth of its octave (at most 6.25% more).
    The index's row count and the fill's capacity vary by a fraction of
    a percent between graphs and label sets of one size, so they mostly
    land on one value, and share one compiled program: a persistent
    compile cache then serves the next graph too."""
    step = 1 << max(0, int(x).bit_length() - 5)
    return -(-int(x) // step) * step


@functools.partial(jax.jit, static_argnames=("rows", "width"))
def _slot_rows(src, off, *, rows: int, width: int):
    """Sort the packed slots by donor and lay each donor's positions out
    in its rows: the k-th slot (in sorted order) lands at flat index
    k + off[donor] of a (rows, width) table.  Padded slots carry donor
    n, and off[n] puts them past the table; unused lanes and the rows
    past the last donor's keep the out-of-range position src.size."""
    flat = src.reshape(-1)
    k = jnp.arange(flat.size, dtype=jnp.int32)
    donor, pos = jax.lax.sort((flat, k), num_keys=1, is_stable=False)
    table = jnp.full(rows * width, flat.size, jnp.int32)
    return table.at[k + off[donor]].set(
        pos, mode="drop", unique_indices=True,
        indices_are_sorted=True).reshape(rows, width)


@functools.partial(jax.jit, static_argnames=("shape",))
def _fill(slot_rows, sel, lab, *, shape):
    """-1 at every slot, then lab[i] at each position of row sel[i];
    out-of-range positions (unused lanes, the padding rows) are dropped.
    The writes are taken lane-major, (width, rows), because a (rows, 48)
    int32 array flattened row-major costs XLA's TPU compiler about a
    minute of relayout; the scatter takes them in any order."""
    pos = slot_rows[sel].T.reshape(-1)
    cls = jnp.broadcast_to(lab[None, :], (slot_rows.shape[1], lab.size))
    return jnp.full(int(np.prod(shape)), -1, jnp.int32).at[pos].set(
        cls.reshape(-1), mode="drop").reshape(shape)


@dataclass
class DonorIndex:
    """Each donor's packed slots, in rows of `width` flat positions.

    slot_rows   (> rows, width) int32 on the device, donor-major; a
                donor's rows are consecutive.  Unused lanes and the rows
                past the last donor's (padding rows, `_bucket`) hold
                `slots`, out of range.
    row_donor   (rows,) int32 on the host: each row's donor.
    capacity    selected rows the fill was last run for; the fill's
                program is compiled per capacity (`fill`).
    """

    slot_rows: jax.Array
    row_donor: np.ndarray
    slots: int
    capacity: int = 0

    @property
    def width(self) -> int:
        return int(self.slot_rows.shape[1])

    @classmethod
    def build(cls, src: jax.Array, counts: np.ndarray) -> "DonorIndex":
        """Index the packed donors `src` (padded slots hold donor n =
        counts.size), where counts[j] is how many real slots donor j has
        (`Plan.donor_counts`)."""
        width = row_width(counts)
        per = -(-counts // width)                   # rows of each donor
        rows, slots = int(per.sum()), int(src.size)
        size = _bucket(rows + 1) * width            # a padding row at least
        if slots + size >= 2 ** 31:
            raise ValueError(f"{slots} slots in {rows} rows of {width} "
                             "overflow int32 positions")
        off = (np.cumsum(per) - per) * width - (np.cumsum(counts) - counts)
        off = np.append(off, size).astype(np.int32)
        table = _slot_rows(src, jnp.asarray(off), rows=size // width,
                           width=width)
        return cls(table, np.repeat(np.arange(counts.size, dtype=np.int32),
                                    per), slots)

    def labeled_rows(self, Y: np.ndarray) -> np.ndarray:
        """The rows whose donor carries a label in Y (host labels)."""
        return np.flatnonzero((Y >= 0)[self.row_donor])

    def fill(self, Y: np.ndarray, sel: np.ndarray, shape) -> jax.Array:
        """The packed slots' classes, of `shape`: Y's label on every slot
        of the rows `sel` (`labeled_rows(Y)`), -1 on all others.

        sel is padded with a padding row up to `capacity`, which is
        kept while sel fits it and fills more than half of it, else set
        to sel's size plus a quarter, `_bucket`ed: label sets of nearly
        the same size share one compiled program."""
        need = sel.size
        if not need <= self.capacity < 2 * need:
            self.capacity = _bucket(need + need // 4 + 1)
        rows = np.full(self.capacity, self.row_donor.size, np.int32)
        rows[:need] = sel
        lab = np.full(self.capacity, -1, np.int32)
        lab[:need] = Y[self.row_donor[sel]]
        return _fill(self.slot_rows, rows, lab, shape=tuple(shape))
