"""Inputs made from the seed: the Erdos-Renyi graph, the labels and the
label churn.

`erdos_renyi` and `make_labels` are copies of the program's
`repro.graph.generators.erdos_renyi` and `repro.graph.edges.make_labels`,
kept here so that a later change to the program cannot change the
inputs.
"""
from __future__ import annotations

import numpy as np


def erdos_renyi(n: int, s: int, seed: int):
    """G(n, s): s directed edges with uniform random endpoints and unit
    weights; returns (u, v, w) (copy of
    `repro.graph.generators.erdos_renyi`, unweighted)."""
    rng = np.random.default_rng(seed)
    u = rng.integers(0, n, size=s, dtype=np.int32)
    v = rng.integers(0, n, size=s, dtype=np.int32)
    return u, v, np.ones(s, np.float32)


def true_labels(n: int, K: int, rng: np.random.Generator) -> np.ndarray:
    """Every node's class, uniform over K: what the labeled share and
    the churn reveal."""
    return rng.integers(0, K, size=n, dtype=np.int32)


def make_labels(n: int, K: int, labeled_frac: float,
                rng: np.random.Generator, true_labels=None) -> np.ndarray:
    """`labeled_frac` of the nodes, chosen uniformly, get their true
    label (or a uniform one); -1 elsewhere (copy of
    `repro.graph.edges.make_labels`)."""
    Y = np.full(n, -1, np.int32)
    m = max(1, int(n * labeled_frac))
    idx = rng.choice(n, size=m, replace=False)
    if true_labels is not None:
        Y[idx] = true_labels[idx]
    else:
        Y[idx] = rng.integers(0, K, size=m)
    return Y


def churn_labels(Y, truth, K: int, frac: float, rng) -> np.ndarray:
    """Reveal `frac` of all nodes' true labels and flip `frac` of the
    known ones to a uniform class (as the chip smoke run's refit)."""
    n = Y.shape[0]
    Y2 = Y.copy()
    reveal = rng.choice(n, max(1, int(n * frac)), replace=False)
    Y2[reveal] = truth[reveal]
    known = np.flatnonzero(Y2 >= 0)
    flip = rng.choice(known, max(1, int(known.size * frac)), replace=False)
    Y2[flip] = rng.integers(0, K, flip.size)
    return Y2
