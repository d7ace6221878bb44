"""Shared-inverse CI sweep kernel — cuPC-S's inner j-loop, fused.

Given the per-set shared quantities from cholinv (G, u_i, var_i), test all
neighbour slots p of the row against the SAME conditioning set:

    num   = C_ij − C(j,S)·u_i
    var_j = 1 − C(j,S)·G·C(j,S)
    indep = Z(num/√(var_i·var_j)) ≤ τ   ∧ mask     (Z = cit.fisher_z)

Fusing the quadratic form with the Fisher-z threshold keeps every
intermediate in VREGs; nothing but the final bit per (set, slot) is written
back to HBM. Layout matches cholinv: lanes = sets, p unrolled per block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.cit import fisher_z
from .backend import resolve_interpret


def _cisweep_kernel(
    tau_ref, g_ref, u_ref, var_ref, cjs_ref, cij_ref, mask_ref, out_ref, *, ell: int,
    bp: int,
):
    tau = tau_ref[0]
    var_i = var_ref[...]
    u = [u_ref[i] for i in range(ell)]
    g = [[g_ref[i, j] for j in range(ell)] for i in range(ell)]
    for p in range(bp):
        w = [cjs_ref[p, i] for i in range(ell)]
        num = cij_ref[p]
        var_j = 1.0
        for i in range(ell):
            num = num - w[i] * u[i]
            var_j = var_j - w[i] * w[i] * g[i][i]
            for j in range(i + 1, ell):
                var_j = var_j - 2.0 * w[i] * w[j] * g[i][j]
        rho = num * jax.lax.rsqrt(jnp.maximum(var_i * var_j, 1e-20))
        # widen the uint8 mask first: the chip has no 8-bit compare
        indep = (fisher_z(rho) <= tau) & (mask_ref[p].astype(jnp.int32) > 0)
        out_ref[p] = indep.astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("ell", "bs", "bp", "interpret"))
def cisweep_kernel(
    g: jax.Array, u_i: jax.Array, var_i: jax.Array, cj_s: jax.Array,
    cij: jax.Array, mask: jax.Array, tau: float, *, ell: int, bs: int = 8,
    bp: int = 8, interpret: bool | None = None,
):
    """g:(ℓ,ℓ,Bs,128) u:(ℓ,Bs,128) var:(Bs,128) cj_s:(P,ℓ,Bs,128)
    cij/mask:(P,Bs,128) → indep (P,Bs,128) uint8. P % bp == Bs % bs == 0.
    interpret=None auto-detects the backend (interpret mode off-TPU)."""
    interpret = resolve_interpret(interpret)
    p_total, _, bs_total, lane = cj_s.shape
    grid = (bs_total // bs, p_total // bp)
    tau_arr = jnp.asarray(tau, jnp.float32).reshape(1)
    return pl.pallas_call(
        functools.partial(_cisweep_kernel, ell=ell, bp=bp),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((ell, ell, bs, lane), lambda b, p: (0, 0, b, 0)),
            pl.BlockSpec((ell, bs, lane), lambda b, p: (0, b, 0)),
            pl.BlockSpec((bs, lane), lambda b, p: (b, 0)),
            pl.BlockSpec((bp, ell, bs, lane), lambda b, p: (p, 0, b, 0)),
            pl.BlockSpec((bp, bs, lane), lambda b, p: (p, b, 0)),
            pl.BlockSpec((bp, bs, lane), lambda b, p: (p, b, 0)),
        ],
        out_specs=pl.BlockSpec((bp, bs, lane), lambda b, p: (p, b, 0)),
        out_shape=jax.ShapeDtypeStruct((p_total, bs_total, lane), jnp.uint8),
        interpret=interpret,
    )(tau_arr, g, u_i, var_i, cj_s, cij, mask)
