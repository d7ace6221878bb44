"""Pure-jnp oracles for every Pallas kernel in this package.

Each kernel's interpret-mode output is asserted allclose against these in
tests/test_kernels.py over shape/dtype sweeps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.cit import fisher_z

_BIG = jnp.int32(2**30)


def corr_ref(x: jax.Array) -> jax.Array:
    """Correlation matrix from raw samples x (m, n), fp32."""
    x = x.astype(jnp.float32)
    xc = x - jnp.mean(x, axis=0, keepdims=True)
    std = jnp.sqrt(jnp.mean(xc * xc, axis=0, keepdims=True))
    xn = xc / jnp.maximum(std, 1e-30)
    c = xn.T @ xn / x.shape[0]
    n = x.shape[1]
    return jnp.clip(c, -1, 1).at[jnp.arange(n), jnp.arange(n)].set(1.0)


def level0_ref(c: jax.Array, tau: float) -> jax.Array:
    keep = fisher_z(c) > tau
    return keep & ~jnp.eye(c.shape[0], dtype=bool)


def level1_dense_ref(c: jax.Array, adj: jax.Array, tau: float):
    """Dense level-1 sweep: for every alive edge (i,j), test every
    k ∈ adj(i) ∪ adj(j), k ∉ {i,j} with the closed-form ρ(i,j|k).

    Returns (removed (n,n) bool — separator found in the union pool,
    kwin (n,n) int32 — min separating k restricted to the ROW-LOCAL pool
    adj(i) \\ {j}, or 2^30). kwin is row-local so the driver's commit can
    rank it within row i's compacted neighbour list and replay the chunked
    S engine's deterministic (rank, endpoint-order) sepset winner.
    """
    n = c.shape[0]
    adj = adj.astype(bool)
    cik = c[:, None, :]  # (i,1,k)
    cjk = c[None, :, :]  # (1,j,k)
    num = c[:, :, None] - cik * cjk
    den = jnp.sqrt(
        jnp.maximum((1.0 - cik * cik) * (1.0 - cjk * cjk), 1e-20)
    )
    indep = fisher_z(num / den) <= tau  # (i,j,k)

    ks = jnp.arange(n)
    k_own = adj[:, None, :]  # k nbr of i (G')
    neq = (ks[None, None, :] != jnp.arange(n)[:, None, None])
    neq &= (ks[None, None, :] != jnp.arange(n)[None, :, None])
    kmask = (k_own | adj[None, :, :]) & neq  # k nbr of i or j (G')
    alive = adj & ~jnp.eye(n, dtype=bool)
    sep = indep & kmask & alive[:, :, None]
    removed = jnp.any(sep, axis=-1)
    sep_own = indep & k_own & neq & alive[:, :, None]
    kwin = jnp.min(jnp.where(sep_own, ks[None, None, :], _BIG), axis=-1)
    return removed, kwin.astype(jnp.int32)


def cholinv_ref(m2: jax.Array, ci_s: jax.Array, jitter: float = 1e-8):
    """Batched SPD inverse + shared vectors. m2: (B,ℓ,ℓ), ci_s: (B,ℓ).
    Returns (g (B,ℓ,ℓ), u_i (B,ℓ), var_i (B,))."""
    eye = jnp.eye(m2.shape[-1], dtype=m2.dtype)
    g = jnp.linalg.inv(m2 + jitter * eye)
    u = jnp.einsum("bxy,by->bx", g, ci_s)
    var_i = 1.0 - jnp.einsum("bx,bx->b", ci_s, u)
    return g, u, var_i


def cisweep_ref(g, u_i, var_i, cj_s, cij, mask, tau: float):
    """Shared-inverse CI sweep. g:(B,ℓ,ℓ) u_i:(B,ℓ) var_i:(B,)
    cj_s:(B,P,ℓ) cij:(B,P) mask:(B,P) → indep&mask (B,P) bool."""
    num = cij - jnp.einsum("bpl,bl->bp", cj_s, u_i)
    gw = jnp.einsum("bxy,bpy->bpx", g, cj_s)
    var_j = 1.0 - jnp.einsum("bpx,bpx->bp", cj_s, gw)
    rho = num / jnp.sqrt(jnp.maximum(var_i[:, None] * var_j, 1e-20))
    return (fisher_z(rho) <= tau) & mask
