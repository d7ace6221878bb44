"""Level-0 kernel (paper Alg. 3): adjacency = Z(C) > τ, elementwise.

One fused pass over VMEM tiles of C; the diagonal is masked with a 2-D iota
against the global tile offsets (no host-side eye matrix).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.cit import fisher_z
from .backend import resolve_interpret


def _level0_kernel(tau_ref, c_ref, o_ref, *, bi: int, bj: int):
    tau = tau_ref[0]
    z = fisher_z(c_ref[...])
    ri = pl.program_id(0) * bi + jax.lax.broadcasted_iota(jnp.int32, (bi, bj), 0)
    cj = pl.program_id(1) * bj + jax.lax.broadcasted_iota(jnp.int32, (bi, bj), 1)
    o_ref[...] = ((z > tau) & (ri != cj)).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("bi", "bj", "interpret"))
def level0_kernel(c: jax.Array, tau: float, *, bi: int = 256, bj: int = 256, interpret: bool | None = None):
    """c: (n, n) fp32 with n % bi == n % bj == 0 (ops.py pads). → uint8 adj.
    interpret=None auto-detects the backend (interpret mode off-TPU)."""
    interpret = resolve_interpret(interpret)
    n = c.shape[0]
    tau_arr = jnp.asarray(tau, jnp.float32).reshape(1)
    return pl.pallas_call(
        functools.partial(_level0_kernel, bi=bi, bj=bj),
        grid=(n // bi, n // bj),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bi, bj), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bi, bj), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.uint8),
        interpret=interpret,
    )(tau_arr, c)
