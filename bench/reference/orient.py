"""Plain skeleton-to-CPDAG orientation: the benchmark's reference for the
orientation step, in numpy, importing nothing of the program.

The rules (Meek 1995; Kalisch & Buehlmann 2007, the form pcalg uses):

1. every unshielded triple i - k - j (i, j not adjacent) with k outside
   SepSet(i, j) becomes i -> k <- j. Where two triples ask for both k -> i
   and i -> k, the edge stays undirected.
2. Meek's rules R1 to R4, each sweep reading one graph and applying every
   firing at once; a pair of firings that orient one edge both ways
   cancels. Sweeps repeat until the graph stops changing.

R1: a -> b, b - c, a and c not adjacent: b -> c.
R2: a -> c -> b and a - b: a -> b.
R3: a - b, a - c, a - d, c -> b, d -> b, c and d not adjacent: a -> b.
R4: a - b, a - d, d -> c, c -> b, a and c adjacent: a -> b.

The digraph D has D[i, j] = D[j, i] = True for an undirected edge and
D[i, j] alone for i -> j.
"""
from __future__ import annotations

import numpy as np


def _any_path(a, b) -> np.ndarray:
    """(a @ b) > 0 for boolean matrices."""
    return (a.astype(np.float32) @ b.astype(np.float32)) > 0


def v_structures(adj: np.ndarray, sep: np.ndarray) -> np.ndarray:
    """Rule 1 on skeleton adj with sepsets sep (n, n, depth), -1 padded."""
    n = adj.shape[0]
    into = np.zeros((n, n), bool)  # into[i, k]: orient i -> k
    for k in range(n):
        nb = np.flatnonzero(adj[k])
        if nb.size < 2:
            continue
        p, q = nb[:, None], nb[None, :]
        pair = (p != q) & ~adj[p, q]
        pair &= ~(sep[p, q] == k).any(axis=-1)
        into[nb, k] = pair.any(axis=1)
    both = into & into.T
    d = adj & ~(into.T & ~both)
    return np.where(both, adj, d)


def _meek_sweep(d: np.ndarray) -> np.ndarray:
    n = d.shape[0]
    und = d & d.T
    dire = d & ~d.T
    adj_any = d | d.T
    nonadj = ~adj_any & ~np.eye(n, dtype=bool)
    r1 = _any_path(dire.T, nonadj) & und
    r2 = _any_path(dire, dire) & und
    r3 = np.zeros_like(d)
    for a in np.flatnonzero(und.any(axis=1)):
        nb = np.flatnonzero(und[a])
        if nb.size < 2:
            continue
        pair = nonadj[nb[:, None], nb[None, :]]
        into_b = dire[nb]  # (k, n): c -> b
        r3[a] = (_any_path(pair, into_b) & into_b).any(axis=0)
    r3 &= und
    r4 = _any_path(_any_path(und, dire) & adj_any, dire) & und
    orient = r1 | r2 | r3 | r4
    orient &= ~orient.T
    return d & ~orient.T


def cpdag(adj: np.ndarray, sep: np.ndarray, max_sweeps: int | None = None) -> np.ndarray:
    """CPDAG digraph of skeleton adj with sepsets sep."""
    d = v_structures(np.asarray(adj, bool), np.asarray(sep))
    for _ in range(max_sweeps or d.shape[0] ** 2):
        nxt = _meek_sweep(d)
        if (nxt == d).all():
            break
        d = nxt
    return d
