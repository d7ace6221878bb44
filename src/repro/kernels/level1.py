"""Fused dense level-1 kernel — the beyond-paper ℓ=1 specialisation.

ρ(i,j|k) = (C_ij − C_ik·C_jk) / √((1−C_ik²)(1−C_jk²)) needs NO matrix
inverse, so the entire level collapses to an elementwise cube swept in
(bi, bj, bk) VMEM tiles (Fig. 6 of the paper shows ℓ=1 is 49–83 % of total
runtime — this kernel erases it). Grid (n/bi, n/bj, n/bk) with k innermost;
two scratch accumulators carry the per-edge `any separator` flag and the
minimum separating k (for SepSet) across k-steps.

Work filter (paper §4.1 early termination): cells are masked by
adjacency — k must neighbour i or j in G′, edge (i,j) must still be alive.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.cit import fisher_z
from .backend import resolve_interpret

_BIG = 2**30  # python int: jnp consts must not be captured by kernels


def _level1_kernel(
    tau_ref, c_ij_ref, c_ik_ref, c_jk_ref, adj_ij_ref, adj_ik_ref, adj_jk_ref,
    rem_ref, kwin_ref, found_acc, kmin_acc, *, bi: int, bj: int,
    bk: int, k_steps: int,
):
    tau = tau_ref[0]
    @pl.when(pl.program_id(2) == 0)
    def _init():
        found_acc[...] = jnp.zeros_like(found_acc)
        kmin_acc[...] = jnp.full_like(kmin_acc, _BIG)

    cij = c_ij_ref[...]  # (bi, bj)
    cik = c_ik_ref[...]  # (bi, bk)
    cjk = c_jk_ref[...]  # (bj, bk)

    num = cij[:, :, None] - cik[:, None, :] * cjk[None, :, :]
    den2 = (1.0 - cik * cik)[:, None, :] * (1.0 - cjk * cjk)[None, :, :]
    rho = num * jax.lax.rsqrt(jnp.maximum(den2, 1e-20))
    indep = fisher_z(rho) <= tau  # (bi, bj, bk)

    # masks: k ≠ i, k ≠ j; edge alive. `found` uses k ∈ adj(i) ∪ adj(j) (the
    # union of both endpoints' candidate pools — what decides removal);
    # `kwin` is restricted to the ROW-LOCAL pool k ∈ adj(i) so the host
    # commit can rank it inside row i's compacted neighbour list and replay
    # the chunked S engine's deterministic (rank, endpoint-order) winner.
    # Every reshape and compare runs on int32: Mosaic cannot relayout bool
    # vectors into 3-D and the chip has no 8-bit compare.
    a_ik = adj_ik_ref[...].astype(jnp.int32)[:, None, :]  # (bi, 1, bk)
    a_jk = adj_jk_ref[...].astype(jnp.int32)[None, :, :]  # (1, bj, bk)
    a_ij = adj_ij_ref[...].astype(jnp.int32)[:, :, None]  # (bi, bj, 1)
    cube = (bi, bj, bk)
    gi = pl.program_id(0) * bi + jax.lax.broadcasted_iota(jnp.int32, (bi, bk), 0)
    gj = pl.program_id(1) * bj + jax.lax.broadcasted_iota(jnp.int32, (bj, bk), 0)
    gk = pl.program_id(2) * bk + jax.lax.broadcasted_iota(jnp.int32, (bj, bk), 1)
    gi3 = jnp.broadcast_to(gi[:, None, :], cube)
    gk3 = jnp.broadcast_to(gk[None, :, :], cube)
    neq = (gk3 != gi3) & (gk3 != jnp.broadcast_to(gj[None, :, :], cube))
    live = indep & neq & (jnp.broadcast_to(a_ij, cube) > 0)
    k_own = jnp.broadcast_to(a_ik, cube) > 0
    sep = live & (k_own | (jnp.broadcast_to(a_jk, cube) > 0))
    found_acc[...] = jnp.maximum(
        found_acc[...], jnp.max(jnp.where(sep, 1, 0), axis=-1)
    )
    kmin_acc[...] = jnp.minimum(
        kmin_acc[...], jnp.min(jnp.where(live & k_own, gk3, _BIG), axis=-1)
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _done():
        rem_ref[...] = found_acc[...]
        kwin_ref[...] = kmin_acc[...]


@functools.partial(jax.jit, static_argnames=("bi", "bj", "bk", "interpret"))
def level1_dense_kernel(
    c: jax.Array, adj: jax.Array, tau: float, *, bi: int = 8, bj: int = 128,
    bk: int = 128, interpret: bool | None = None,
):
    """c: (n,n) fp32, adj: (n,n) uint8 (G′ snapshot), n % lcm(bi,bj,bk) == 0.

    Returns (removed (n,n) int32 0/1 — separator exists in adj(i) ∪ adj(j);
    kwin (n,n) int32 — min separating k ∈ adj(i) \\ {j}, else 2^30).
    interpret=None auto-detects the backend (interpret mode off-TPU)."""
    interpret = resolve_interpret(interpret)
    n = c.shape[0]
    k_steps = n // bk
    grid = (n // bi, n // bj, k_steps)
    kern = functools.partial(
        _level1_kernel, bi=bi, bj=bj, bk=bk, k_steps=k_steps
    )
    tau_arr = jnp.asarray(tau, jnp.float32).reshape(1)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bi, bj), lambda i, j, k: (i, j)),  # C_ij
            pl.BlockSpec((bi, bk), lambda i, j, k: (i, k)),  # C_ik
            pl.BlockSpec((bj, bk), lambda i, j, k: (j, k)),  # C_jk
            pl.BlockSpec((bi, bj), lambda i, j, k: (i, j)),  # adj_ij
            pl.BlockSpec((bi, bk), lambda i, j, k: (i, k)),  # adj_ik
            pl.BlockSpec((bj, bk), lambda i, j, k: (j, k)),  # adj_jk
        ],
        out_specs=[
            pl.BlockSpec((bi, bj), lambda i, j, k: (i, j)),
            pl.BlockSpec((bi, bj), lambda i, j, k: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, n), jnp.int32),
            jax.ShapeDtypeStruct((n, n), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bi, bj), jnp.int32),
            pltpu.VMEM((bi, bj), jnp.int32),
        ],
        interpret=interpret,
    )(tau_arr, c, c, c, adj, adj, adj)
