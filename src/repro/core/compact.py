"""Row compaction of the adjacency matrix (paper §3.3, Fig. 2).

A_G (n×n dense 0/1) → A'_G (n×n′ int32), where row i lists the column indices
of Vi's neighbours left-justified, padded with -1, plus a per-row count n'_i.
The CUDA version uses a parallel stream-compaction (scan); on TPU a masked
argsort achieves the same in one fused XLA op and is trivially sharded by
rows. n′ (max row degree) bounds the worklist shapes for the level.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def compact_rows(adj: jax.Array, n_prime: int | None = None):
    """Compact each row of a boolean adjacency matrix.

    Returns (compact, counts):
      compact: (rows, n′) int32, neighbour column ids, -1 padded
      counts:  (rows,)    int32, n'_i

    adj may be a block of rows (rows, n) of the (n, n) matrix. n_prime:
    static output width. If None, uses n (fully dynamic callers should pass
    the previous level's bound to keep shapes tight).
    """
    n = adj.shape[-1]
    width = n if n_prime is None else n_prime
    adj = adj.astype(bool)
    counts = jnp.sum(adj, axis=1).astype(jnp.int32)
    # stable sort of column ids with non-neighbours pushed to the end
    col = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), adj.shape)
    key = jnp.where(adj, col, n)
    order = jnp.sort(key, axis=1)[:, :width]
    compact = jnp.where(order == n, jnp.int32(-1), order)
    return compact, counts


def compact_rows_np(adj: np.ndarray):
    """Host reference of compact_rows (oracle for tests)."""
    n = adj.shape[0]
    counts = adj.sum(axis=1).astype(np.int32)
    out = -np.ones((n, n), dtype=np.int32)
    for i in range(n):
        nbrs = np.flatnonzero(adj[i])
        out[i, : len(nbrs)] = nbrs
    return out, counts
