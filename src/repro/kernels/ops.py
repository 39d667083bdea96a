"""Jitted wrappers around the Pallas kernels.

``gee_pallas`` packs edges into destination-sorted uniform blocks
(host-side, static shapes) and dispatches the gee_scatter kernel; it is
the TPU hot path behind ``repro.core.gee`` when running on real
hardware.  On CPU the kernels execute in interpret mode (Python
evaluation of the kernel body) — correctness-equivalent,
performance-irrelevant.
"""
from __future__ import annotations

from typing import Union

import jax.numpy as jnp
import numpy as np

from repro.core.gee import edge_contributions, make_w
from repro.kernels.gee_scatter import (EDGE_BLOCK, TILE_N,
                                       gee_scatter_pallas)
from repro.kernels import flash_attention as fa


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def pack_edges(dst, cls, val, n: int, tile_n: int = TILE_N,
               edge_block: int = EDGE_BLOCK, pad_cls: int = 0):
    """Sort contributions by destination tile and pack into uniform
    (T, BPT, 1, EB) blocks (the unit axis is the kernels' lane-dense
    block row, see `gee_scatter`).  Host-side numpy (static output
    shapes depend on the max bucket size).  Padded slots: val = 0,
    cls = pad_cls."""
    dst = np.asarray(dst)
    cls = np.asarray(cls)
    val = np.asarray(val)
    T = _round_up(n, tile_n) // tile_n
    tile = dst // tile_n
    order = np.argsort(tile, kind="stable")
    tile_s, dst_s, cls_s, val_s = tile[order], dst[order], cls[order], \
        val[order]
    counts = np.bincount(tile_s, minlength=T)
    bpt = max(1, int(np.ceil(counts.max() / edge_block)))
    slots = T * bpt * edge_block
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    pos = np.arange(dst_s.shape[0]) - starts[tile_s]
    slot = tile_s * (bpt * edge_block) + pos

    rows_buf = np.zeros(slots, np.int32)
    cls_buf = np.full(slots, pad_cls, np.int32)
    val_buf = np.zeros(slots, np.float32)
    rows_buf[slot] = dst_s - tile_s * tile_n
    cls_buf[slot] = cls_s
    val_buf[slot] = val_s
    shape = (T, bpt, 1, edge_block)
    return (rows_buf.reshape(shape), cls_buf.reshape(shape),
            val_buf.reshape(shape), T)


def gee_pallas(u, v, w, Y, *, K: int, n: int, tile_n: int = TILE_N,
               edge_block: int = EDGE_BLOCK,
               interpret: Union[bool, str] = "auto",
               pad_k: int = 8) -> jnp.ndarray:
    """GEE via the Pallas scatter kernel. Returns Z (n, K) float32."""
    Wv = make_w(jnp.asarray(Y), K)
    dst, cls, val = edge_contributions(
        jnp.asarray(u), jnp.asarray(v), jnp.asarray(w, jnp.float32),
        jnp.asarray(Y), Wv)
    kdim = _round_up(K, pad_k)
    rows, clsb, valb, T = pack_edges(dst, cls, val, n, tile_n, edge_block)
    Z = gee_scatter_pallas(jnp.asarray(rows), jnp.asarray(clsb),
                           jnp.asarray(valb), num_tiles=T, tile_n=tile_n,
                           kdim=kdim, interpret=interpret)
    return Z[:n, :K]


def flash_attention(q, k, v, *, bq: int = fa.DEFAULT_BQ,
                    bk: int = fa.DEFAULT_BK,
                    interpret: Union[bool, str] = "auto"):
    return fa.flash_attention(q, k, v, bq=bq, bk=bk, interpret=interpret)
