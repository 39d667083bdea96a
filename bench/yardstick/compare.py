"""The numbers that decide `correct`: each compares what the timed path
produced with the plain reference (`ref`)."""
from __future__ import annotations

import math

import numpy as np

INF = math.inf


def rel_err(Z, ref, block: int = 1 << 18) -> float:
    """Largest elementwise relative error of Z against the reference.
    An entry the reference holds at exactly 0 must be exactly 0 (GEE
    sums positive terms, so nothing cancels); a shape mismatch or a
    non-finite entry reads as infinite."""
    Z = np.asarray(Z)
    ref = np.asarray(ref)
    if Z.shape != ref.shape:
        return INF
    worst = 0.0
    for lo in range(0, max(Z.shape[0], 1), block):
        z = Z[lo:lo + block].astype(np.float64)
        r = ref[lo:lo + block].astype(np.float64)
        if not np.isfinite(z).all():
            return INF
        d = np.abs(z - r)
        a = np.abs(r)
        nz = a > 0
        if np.any(d[~nz] > 0):
            return INF
        if nz.any():
            worst = max(worst, float((d[nz] / a[nz]).max()))
    return worst
