"""Grid-resident cuPC-S kernel: the rank axis as a sequential Pallas grid dim.

The chunked engines (cholinv + cisweep) dispatch one fused program per
rank-chunk from the host and reduce the (n, T, n′) ``sep_found`` tensor to
per-(row, slot) winners in XLA — one host dispatch (and one HBM round-trip
of ``sep_found``) per chunk. This kernel folds the whole rank loop into ONE
``pallas_call``:

  * grid = (row-lane groups, neighbour-slot blocks, rank steps): rows live
    on the 128 vector lanes, ranks stream through the sublane axis 8 at a
    time; the rank-step dim is innermost, so consecutive steps revisit the
    same output block. Slots come ``bp`` at a time (:func:`slot_block`):
    the body unrolls over them, so a whole n′ ≈ 1.6k row would blow both
    the compile time and the 16 MiB scoped VMEM;
  * the winner arrays accumulate ACROSS grid steps in the output blocks
    (index maps independent of the rank step — the canonical Pallas
    reduction pattern): ``t_win`` as the min separating local rank and
    ``s_win`` as the conditioning-set ids at that rank, selected in-kernel;
  * nothing per-(row, rank, slot) ever returns to HBM — only the final
    (n′, n) winner tiles, so a launch may cover every rank of a level while
    staying inside the same VMEM working set as one old chunk.

Winner semantics replicate ``levels._winners`` exactly: the minimum
separating rank per (row, slot) wins, and ``s_win`` is the set at that rank
(ranks are distinct within a launch, so the in-kernel one-hot select is
exact). Ranks are tracked as *launch-local* int32 offsets — the wrapper
adds the launch base ``t0`` back in the rank dtype, which is what keeps the
kernel int32-clean even when x64 ranks are enabled (levels.plan_level caps
chunk lengths so local offsets always fit).

The per-set inverse mirrors the jnp engine branch-for-branch (ℓ=1 scalar
reciprocal, ℓ=2 closed-form adjugate as in ``levels._inv_spd``, ℓ≥3
unrolled Cholesky as in ``kernels/cholinv.py``), with the same
diagonal-scaled Tikhonov jitter. Off-TPU the kernel executes in Pallas
interpret mode (lax.while_loop over the grid — the body traces once), so
CI exercises the identical accumulation semantics on CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.cit import fisher_z
from .backend import resolve_interpret

#: "no separating set found" marker for the launch-local int32 rank — same
#: ≥ 2^30 convention as the dense ℓ=1 kernel's kwin.
SENTINEL = 2**30


def slot_block(npr: int, ell: int) -> int:
    """Neighbour slots per grid step: all of them when few, else a power of
    two that keeps the (slots, ℓ, 8, 128) fp32 block ≤ 512 KiB (≥ 8)."""
    cap = 1 << (max(8, 128 // ell).bit_length() - 1)
    return npr if npr <= cap else cap


def _inverse_tiles(m2_ref, *, ell: int, jitter: float):
    """g[i][j] tiles of the jittered SPD inverse, mirroring the jnp engine:
    ℓ=1 reciprocal (levels.ci_sweep), ℓ=2 adjugate (levels._inv_spd fast
    path), ℓ≥3 Cholesky → L⁻¹ → Gram (kernels/cholinv.py). The jitter is
    scaled by the block's mean diagonal so regularisation is relative to
    the block's magnitude (for correlation blocks the scale is exactly 1)."""
    if ell == 1:
        return [[1.0 / jnp.maximum(m2_ref[0, 0], 1e-8)]]

    scale = m2_ref[0, 0]
    for i in range(1, ell):
        scale = scale + m2_ref[i, i]
    jit_eff = jitter * (scale * (1.0 / ell))

    if ell == 2:
        a = m2_ref[0, 0] + jit_eff
        b = m2_ref[0, 1]
        c = m2_ref[1, 0]
        d = m2_ref[1, 1] + jit_eff
        det = a * d - b * c
        return [[d / det, -b / det], [-c / det, a / det]]

    a = [[m2_ref[i, j] + (jit_eff if i == j else 0.0) for j in range(ell)]
         for i in range(ell)]
    eps = 1e-20
    l = [[None] * ell for _ in range(ell)]
    for j in range(ell):
        s = a[j][j]
        for k in range(j):
            s = s - l[j][k] * l[j][k]
        l[j][j] = jnp.sqrt(jnp.maximum(s, eps))
        inv_ljj = 1.0 / l[j][j]
        for i in range(j + 1, ell):
            s = a[i][j]
            for k in range(j):
                s = s - l[i][k] * l[j][k]
            l[i][j] = s * inv_ljj
    minv = [[None] * ell for _ in range(ell)]
    for j in range(ell):
        minv[j][j] = 1.0 / l[j][j]
        for i in range(j + 1, ell):
            s = l[i][j] * minv[j][j]
            for k in range(j + 1, i):
                s = s + l[i][k] * minv[k][j]
            minv[i][j] = -s / l[i][i]
    g = [[None] * ell for _ in range(ell)]
    for i in range(ell):
        for j in range(i, ell):
            s = 0.0
            for k in range(j, ell):
                s = s + minv[k][i] * minv[k][j]
            g[i][j] = s
            if i != j:
                g[j][i] = s
    return g


def _sgrid_kernel(
    tau_ref, m2_ref, ci_ref, cjs_ref, cij_ref, mask_ref, sid_ref,
    twin_ref, swin_ref, *, ell: int, bp: int, tb: int,
    jitter: float,
):
    step = pl.program_id(2)  # rank step (innermost → sequential revisits)

    @pl.when(step == 0)
    def _():
        twin_ref[...] = jnp.full_like(twin_ref[...], SENTINEL)
        swin_ref[...] = jnp.zeros_like(swin_ref[...])

    tau = tau_ref[0]
    # shared per-(rank, row) quantities on (tb, 128) = (ranks, rows) tiles
    g = _inverse_tiles(m2_ref, ell=ell, jitter=jitter)
    ci = [ci_ref[i] for i in range(ell)]
    u = [0.0] * ell
    for i in range(ell):
        for j in range(ell):
            u[i] = u[i] + g[i][j] * ci[j]
    var_i = 1.0
    for i in range(ell):
        var_i = var_i - ci[i] * u[i]

    # launch-local ranks of this step, broadcast over rows (lanes)
    t_loc = step * tb + jax.lax.broadcasted_iota(jnp.int32, (tb, 128), 0)

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

        key = jnp.where(indep, t_loc, SENTINEL)          # (tb, 128)
        kmin = jnp.min(key, axis=0, keepdims=True)       # (1, 128)
        prev = twin_ref[p : p + 1, :]
        new = kmin < prev
        twin_ref[p : p + 1, :] = jnp.where(new, kmin, prev)
        # the set at the winning rank: ranks are distinct within the launch,
        # so (key == kmin) is one-hot over sublanes whenever kmin < SENTINEL
        sel = key == kmin
        for e in range(ell):
            # dtype pinned: under x64, jnp.sum would promote int32 → int64
            sval = jnp.sum(
                jnp.where(sel, sid_ref[e], 0), axis=0, keepdims=True,
                dtype=jnp.int32,
            )
            row = p * ell + e
            cur = swin_ref[row : row + 1, :]
            swin_ref[row : row + 1, :] = jnp.where(new, sval, cur)


@functools.partial(
    jax.jit, static_argnames=("ell", "npr", "tb", "jitter", "interpret")
)
def sgrid_kernel(
    m2: jax.Array, ci_s: jax.Array, cj_s: jax.Array, cij: jax.Array,
    mask: jax.Array, s_ids: jax.Array, tau, *, ell: int, npr: int,
    tb: int = 8, jitter: float = 1e-8, interpret: bool | None = None,
):
    """Lane layout: m2 (ℓ,ℓ,T,Nl), ci_s (ℓ,T,Nl), cj_s (n′,ℓ,T,Nl),
    cij (n′,T,Nl) fp32, mask (n′,T,Nl) uint8, s_ids (ℓ,T,Nl) int32 — rows
    on lanes (Nl % 128 == 0), ranks on sublanes (T % tb == 0).
    Returns (t_win (n′, Nl) int32 — min separating launch-local rank,
    SENTINEL when none; s_win (n′·ℓ, Nl) int32 — the set at that rank).
    interpret=None auto-detects the backend (interpret mode off-TPU)."""
    interpret = resolve_interpret(interpret)
    t_total, n_lanes = cij.shape[-2:]
    lane = 128
    bp = slot_block(npr, ell)
    npr_pad = -(-npr // bp) * bp
    if npr_pad != npr:  # padded slots are masked out: they never separate
        def pad(x):
            return jnp.pad(x, [(0, npr_pad - npr)] + [(0, 0)] * (x.ndim - 1))
        cj_s, cij, mask = pad(cj_s), pad(cij), pad(mask)
    grid = (n_lanes // lane, npr_pad // bp, t_total // tb)
    tau_arr = jnp.asarray(tau, jnp.float32).reshape(1)
    twin, swin = pl.pallas_call(
        functools.partial(
            _sgrid_kernel, ell=ell, bp=bp, tb=tb, jitter=jitter
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((ell, ell, tb, lane), lambda g, p, s: (0, 0, s, g)),
            pl.BlockSpec((ell, tb, lane), lambda g, p, s: (0, s, g)),
            pl.BlockSpec((bp, ell, tb, lane), lambda g, p, s: (p, 0, s, g)),
            pl.BlockSpec((bp, tb, lane), lambda g, p, s: (p, s, g)),
            pl.BlockSpec((bp, tb, lane), lambda g, p, s: (p, s, g)),
            pl.BlockSpec((ell, tb, lane), lambda g, p, s: (0, s, g)),
        ],
        out_specs=[
            pl.BlockSpec((bp, lane), lambda g, p, s: (p, g)),
            pl.BlockSpec((bp * ell, lane), lambda g, p, s: (p, g)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((npr_pad, n_lanes), jnp.int32),
            jax.ShapeDtypeStruct((npr_pad * ell, n_lanes), jnp.int32),
        ],
        interpret=interpret,
    )(tau_arr, m2, ci_s, cj_s, cij, mask, s_ids)
    return twin[:npr], swin[: npr * ell]
