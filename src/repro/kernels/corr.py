"""Tiled correlation-matrix kernel: C = Xnᵀ Xn / m on the MXU.

Grid (n/bn, n/bn, m/bm); the sample (contraction) axis is the innermost grid
dimension so the fp32 accumulator scratch lives in VMEM across k-steps.
Block shapes are MXU-aligned (multiples of 128 on the lane axis, 8 on the
sublane axis). Standardisation (mean/std) is done by the ops.py wrapper —
it is O(mn) vs the O(mn²) matmul here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import resolve_interpret


def _corr_kernel(x1_ref, x2_ref, o_ref, acc_ref, *, inv_m: float, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = x1_ref[...]  # (bm, bi) slice of standardized samples
    b = x2_ref[...]  # (bm, bj)
    # fp32 contraction: Mosaic's default rounds the operands to bf16, which
    # put C 5.5e-4 away from a float64 C on a v5e at n=1643, m=850
    acc_ref[...] += jax.lax.dot_general(
        a, b, (((0,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _done():
        o_ref[...] = acc_ref[...] * inv_m


@functools.partial(jax.jit, static_argnames=("bn", "bm", "interpret"))
def corr_matmul(xn: jax.Array, *, bn: int = 256, bm: int = 512, interpret: bool | None = None):
    """xn: (m, n) already standardized (zero mean, unit std); returns XnᵀXn/m.

    m, n must be multiples of bm, bn (ops.py pads). interpret=None
    auto-detects the backend (interpret mode off-TPU).
    """
    interpret = resolve_interpret(interpret)
    m, n = xn.shape
    k_steps = m // bm
    grid = (n // bn, n // bn, k_steps)
    return pl.pallas_call(
        functools.partial(_corr_kernel, inv_m=1.0 / m, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, k: (k, i)),
            pl.BlockSpec((bm, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bn, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bn, bn), jnp.float32)],
        interpret=interpret,
    )(xn, xn)
