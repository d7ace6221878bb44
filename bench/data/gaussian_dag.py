"""The benchmark's own data: the cuPC paper's §5.6 linear-Gaussian DAG law,
with each variable standardised as it is generated.

The law (cuPC, arXiv:1812.08491, §5.6): a lower-triangular adjacency with
independent Bernoulli(d) entries, the ones replaced by U[0.1, 1] weights,
and V_i = N_i + Σ_j W[i, j] V_j with N_i ~ N(0, 1).

Departure: V_i is standardised (zero mean, unit variance over the m
samples) before any later variable reads it. Applied only at the end, as
the plain law does, the variances grow down the topological order and, at
DREAM5-Insilico's size (n=1643, m=850, d=0.05), most pairs of columns are
collinear (|c| > 0.9999 for 63.5% of pairs, rank 759 of 850): the
partial correlations of levels >= 1 are then 0/0 in float32 and rounding,
not the data, decides which edges survive. Standardising as it goes keeps
the weights, the density and the topological order, and keeps the
samples full rank.

Everything is float64 numpy seeded through ``numpy.random.default_rng``.
"""
from __future__ import annotations

import numpy as np


def sample(n: int, m: int, density: float, seed, network=None) -> np.ndarray:
    """(m, n) float64 samples. The DAG and its weights are drawn from
    ``network`` when it is given, else from ``seed``; the noise always from
    ``seed`` (an int or a sequence of ints)."""
    rng = np.random.default_rng(seed)
    net = rng if network is None else np.random.default_rng(network)
    mask = np.tril(net.random((n, n)) < density, k=-1)
    w = np.where(mask, net.uniform(0.1, 1.0, (n, n)), 0.0)
    x = rng.standard_normal((m, n))
    for i in range(n):
        parents = np.flatnonzero(mask[i, :i])
        if parents.size:
            x[:, i] += x[:, parents] @ w[i, parents]
        col = x[:, i] - x[:, i].mean()
        x[:, i] = col / np.sqrt(col @ col / m)
    return x
