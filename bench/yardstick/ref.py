"""The plain reference: GEE's embedding, written from the paper's
definition with numpy alone.

`gee` follows the program's `repro.core.ref_python.gee_numpy` (every
edge (u, v, w) adds Wv[v]*w to Z[u, Y[v]] and Wv[u]*w to Z[v, Y[u]],
Wv = 1/class count of labeled nodes), but sums in float64, so that the
reference's own rounding stays far below what is compared.

The control (`precision="high"`) is the same reference computed as a
float32 one-hot matmul at `Precision.HIGH` would compute it: three
bf16 passes, hi*hi + hi*lo + lo*hi.  The one-hot operand is exact in
bf16, so each value is carried as bf16(v) + bf16(v - bf16(v)).
"""
from __future__ import annotations

import numpy as np


def bf16_round(x) -> np.ndarray:
    """float32 -> nearest bfloat16 (ties to even), kept as float32."""
    x = np.ascontiguousarray(x, np.float32)
    b = x.view(np.uint32)
    b = (b + (((b >> 16) & 1) + 0x7FFF)) & 0xFFFF0000
    return b.astype(np.uint32).view(np.float32)


def high_split(x) -> np.ndarray:
    """What a three-pass bf16 matmul keeps of float32 values x against
    an exact operand: bf16(x) + bf16(x - bf16(x))."""
    x = np.asarray(x, np.float32)
    hi = bf16_round(x)
    return hi + bf16_round(x - hi)


def make_w(Y, K: int) -> np.ndarray:
    """1/class count for labeled nodes, 0 for unlabeled (float32, as
    the program carries it)."""
    Y = np.asarray(Y)
    counts = np.bincount(Y[Y >= 0], minlength=K).astype(np.float64)
    inv = np.where(counts > 0, 1.0 / np.maximum(counts, 1), 0.0)
    return np.where(Y >= 0, inv[np.maximum(Y, 0)], 0.0).astype(np.float32)


def contributions(u, v, w, Y, Wv):
    """(destination row, class, value) of every labeled contribution."""
    u = np.asarray(u, np.int64)
    v = np.asarray(v, np.int64)
    w = np.asarray(w, np.float32)
    dst = np.concatenate([u, v])
    src = np.concatenate([v, u])
    ww = np.concatenate([w, w])
    ys = np.asarray(Y)[src]
    m = ys >= 0
    return dst[m], ys[m].astype(np.int64), (Wv[src[m]] * ww[m])


def gee(u, v, w, Y, K: int, n: int, precision: str = "exact"
        ) -> np.ndarray:
    """Z (n, K) float64.  precision="high" gives the control."""
    Wv = make_w(Y, K)
    dst, cls, val = contributions(u, v, w, Y, Wv)
    if precision == "high":
        val = high_split(val)
    Z = np.bincount(dst * K + cls, weights=val.astype(np.float64),
                    minlength=n * K)
    return Z.reshape(n, K)
