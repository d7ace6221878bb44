"""Pipeline parallelism: GPipe-style stage runner on a ``pipe`` mesh axis.

Stages communicate activations with ``lax.ppermute`` inside ``shard_map``;
microbatches stream through the S-deep pipeline in M + S - 1 ticks. The
runner is forward-only code but fully differentiable — the transpose of
ppermute is the reverse permute, so ``jax.grad`` through
``pipeline_apply`` yields the correct 1F1B-equivalent backward schedule
without hand-written adjoints.

Layout: stage s holds ``params[s]`` (stacked per-stage leaves sharded
over ``pipe`` on dim 0); microbatch stream xs (M, mb, ...) is replicated
— rank 0 injects, rank S-1 emits.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, PartitionSpec as P


def pipeline_apply(stage_fn, stage_params, xs, mesh, axis: str = "pipe"):
    """stage_fn(params_one_stage, x_mb) -> x_mb.
    stage_params: pytree with leading dim S (sharded over ``axis``).
    xs: (M, mb, ...) microbatch stream (replicated). Returns (M, mb, ...)."""
    s_total = mesh.shape[axis]

    def runner(params_local, xs_local):
        # params_local leaves: (1, ...) — this rank's stage
        params_one = jax.tree.map(lambda x: x[0], params_local)
        rank = jax.lax.axis_index(axis)
        m = xs_local.shape[0]
        buf = jnp.zeros_like(xs_local[0])
        outs = jnp.zeros_like(xs_local)
        perm = [(i, (i + 1) % s_total) for i in range(s_total)]

        def tick(carry, t):
            buf_in, outs = carry
            x0 = xs_local[jnp.clip(t, 0, m - 1)]
            inp = jnp.where(rank == 0, x0, buf_in)
            valid_in = (t < m) | (rank > 0)
            out = stage_fn(params_one, inp)
            out = jnp.where(valid_in, out, jnp.zeros_like(out))
            done = t - (s_total - 1)
            write = (rank == s_total - 1) & (done >= 0)
            outs = jnp.where(
                write,
                outs.at[jnp.clip(done, 0, m - 1)].set(out),
                outs,
            )
            buf_next = jax.lax.ppermute(out, axis, perm)
            return (buf_next, outs), None

        # scan (not fori_loop): reverse-mode differentiable — grad through
        # the pipeline gives the correct backward schedule for free.
        (buf, outs), _ = jax.lax.scan(
            tick, (buf, outs), jnp.arange(m + s_total - 1)
        )
        # every rank returns its outs; only the last rank's is real —
        # psum after masking broadcasts it (cheap: one activation-sized
        # all-reduce per call, amortized over all microbatches).
        outs = jnp.where(jax.lax.axis_index(axis) == s_total - 1, outs, 0.0)
        return jax.lax.psum(outs, axis)

    in_specs = (
        jax.tree.map(lambda _: P(axis), stage_params),
        P(),
    )
    # the runner places everything by hand: run it on the mesh's devices
    # with Auto axes, so the output carries no Explicit sharding type that a
    # caller's grad outside any mesh context could not seed
    mesh = Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))
    fn = jax.shard_map(runner, mesh=mesh, in_specs=in_specs, out_specs=P(), check_vma=False)
    return fn(stage_params, xs)


def split_stages(layer_params, n_stages: int):
    """Reshape stacked layer params (L, ...) -> (S, L/S, ...)."""

    def one(x):
        l = x.shape[0]
        assert l % n_stages == 0, f"layers {l} % stages {n_stages} != 0"
        return x.reshape((n_stages, l // n_stages) + x.shape[1:])

    return jax.tree.map(one, layer_params)
