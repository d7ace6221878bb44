"""jit'd public wrappers around the Pallas kernels (padding, layout, fallback).

On non-TPU backends the kernels run in interpret mode (Python semantics on
CPU) — bit-for-bit the algorithm that compiles for TPU. `interpret=None`
auto-detects (kernels/backend.py). The wrappers accept the natural
batch-first layouts used by core/levels.py and do the SoA transposes the
kernels want.

Engine-selection matrix (who calls which kernel; registry in
core/engines.py, jnp engines in core/levels.py):

  engine     ℓ=1                          ℓ≥2                       code path
  ─────────  ───────────────────────────  ────────────────────────  ─────────────────
  S          levels.chunk_s               levels.chunk_s            XLA einsums
  E          levels.chunk_e               levels.chunk_e            XLA einsums
  S-kernel   ops.chunk_s_kernel           ops.chunk_s_kernel_class  cholinv+cisweep
  S-grid     ops.chunk_s_grid             ops.chunk_s_grid          sgrid (rank grid)
  L1-dense   ops.level1_dense             (resolves to S)           level1 cube
  auto       L1-dense                     S-kernel                  fused production

S-kernel at ℓ≥2 runs one program per degree class and rank chunk
(core/levels.run_level_classes). On TPU every ops.* path compiles through
Mosaic; off-TPU the same kernels execute in Pallas interpret mode, so
`auto` stays bit-identical across backends (the XLA gathers feeding the
kernels are backend-native either way). corr.py backs
`pc(x, corr="kernel")`; level0.py is the fused Alg. 3.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import cholinv as _cholinv
from . import cisweep as _cisweep
from . import corr as _corr
from . import level0 as _level0
from . import level1 as _level1
from . import sgrid as _sgrid
from .backend import resolve_interpret as _interp

LANE = 128


def _pad_to(x, mult, axis, value=0.0):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


# ---------------------------------------------------------------- correlation
def correlation(x: jax.Array, *, bn: int = 256, bm: int = 512, interpret=None) -> jax.Array:
    """Correlation matrix from samples x (m, n) via the tiled MXU kernel."""
    m, n = x.shape
    x = x.astype(jnp.float32)
    xc = x - jnp.mean(x, axis=0, keepdims=True)
    std = jnp.sqrt(jnp.mean(xc * xc, axis=0, keepdims=True))
    xn = xc / jnp.maximum(std, 1e-30)
    bm_eff = min(bm, max(LANE, (m // LANE) * LANE)) if m >= LANE else m
    xn = _pad_to(_pad_to(xn, bn, 1), bm_eff, 0)  # zero rows add nothing
    c_raw = _corr.corr_matmul(xn, bn=bn, bm=bm_eff, interpret=_interp(interpret))
    c_raw = c_raw * (xn.shape[0] / m)  # kernel divides by padded m
    c = jnp.clip(c_raw[:n, :n], -1.0, 1.0)
    return c.at[jnp.arange(n), jnp.arange(n)].set(1.0)


# -------------------------------------------------------------------- level 0
def level0(c: jax.Array, tau: float, *, block: int = 256, interpret=None) -> jax.Array:
    n = c.shape[0]
    b = min(block, max(LANE, n))
    cp = _pad_to(_pad_to(c, b, 0), b, 1)
    adj = _level0.level0_kernel(cp, tau, bi=b, bj=b, interpret=_interp(interpret))
    return adj[:n, :n].astype(bool)


# -------------------------------------------------------- level 1 (dense cube)
def level1_dense(c: jax.Array, adj: jax.Array, tau: float, *, interpret=None):
    """Returns (removed (n,n) bool — separator in adj(i) ∪ adj(j); kwin
    (n,n) int32 — min separating k ∈ adj(i) \\ {j}, row-local for the
    deterministic sepset commit in core/levels.commit_dense_l1)."""
    n = c.shape[0]
    bi, bj, bk = 8, min(128, _ceil_mult(n, LANE)), min(128, _ceil_mult(n, LANE))
    cp = _pad_to(_pad_to(c, max(bi, bj, bk), 0), max(bi, bj, bk), 1)
    ap = _pad_to(_pad_to(adj.astype(jnp.uint8), max(bi, bj, bk), 0), max(bi, bj, bk), 1)
    rem, kwin = _level1.level1_dense_kernel(
        cp, ap, tau, bi=bi, bj=bj, bk=bk, interpret=_interp(interpret)
    )
    return rem[:n, :n].astype(bool), kwin[:n, :n]


def _ceil_mult(n, m):
    return ((n + m - 1) // m) * m


# ------------------------------------------------- cuPC-S fused batch (ℓ ≥ 2)
def ci_shared(
    m2: jax.Array, ci_s: jax.Array, cj_s: jax.Array, cij: jax.Array,
    mask: jax.Array, tau: float, *, ell: int, interpret=None,
):
    """Batch-first API: m2 (B,ℓ,ℓ), ci_s (B,ℓ), cj_s (B,P,ℓ), cij/mask (B,P)
    → indep∧mask (B,P) bool. Pads B to 8·128 and P to 8."""
    b, p = cij.shape
    interpret = _interp(interpret)

    bs_mult = 8 * LANE
    b_pad = _ceil_mult(max(b, bs_mult), bs_mult)
    p_pad = _ceil_mult(max(p, 8), 8)
    bs_total = b_pad // LANE

    def soa(x, pad_shape):  # (B, ...) -> (..., Bs, LANE)
        x = jnp.pad(x, [(0, b_pad - b)] + [(0, q) for q in pad_shape])
        perm = tuple(range(1, x.ndim)) + (0,)
        x = jnp.transpose(x, perm)
        return x.reshape(x.shape[:-1] + (bs_total, LANE))

    m2_k = soa(m2.astype(jnp.float32), [0, 0])  # (ℓ,ℓ,Bs,L)
    # SPD-pad the batch tail with identity so Cholesky stays finite
    if b_pad != b:
        eye = jnp.eye(ell, dtype=jnp.float32)
        tail_mask = (jnp.arange(b_pad) >= b).reshape(bs_total, LANE)
        m2_k = jnp.where(tail_mask[None, None], eye[:, :, None, None], m2_k)
    ci_k = soa(ci_s.astype(jnp.float32), [0])  # (ℓ,Bs,L)
    g, u, var = _cholinv.cholinv_kernel(m2_k, ci_k, ell=ell, interpret=interpret)

    cjs_k = soa(cj_s.astype(jnp.float32), [p_pad - p, 0])  # (P,ℓ,Bs,L)
    cij_k = soa(cij.astype(jnp.float32), [p_pad - p])  # (P,Bs,L)
    mask_k = soa(mask.astype(jnp.uint8), [p_pad - p])
    indep = _cisweep.cisweep_kernel(
        g, u, var, cjs_k, cij_k, mask_k, tau, ell=ell, interpret=interpret
    )  # (P,Bs,L) uint8
    out = indep.reshape(p_pad, b_pad).T[:b, :p]
    return out.astype(bool)


# ----------------------------- grid-resident cuPC-S (rank axis in the grid)
def ci_shared_grid(
    m2: jax.Array, ci_s: jax.Array, cj_s: jax.Array, cij: jax.Array,
    mask: jax.Array, s_ids: jax.Array, tau, *, ell: int, interpret=None,
):
    """Grid-resident cuPC-S sweep over a gathered chunk in the natural
    batch-first layout: m2 (n_l,T,ℓ,ℓ), ci_s (n_l,T,ℓ), cj_s (n_l,T,n′,ℓ),
    cij/mask (n_l,T,n′), s_ids (n_l,T,ℓ).

    One ``pallas_call`` covers ALL T ranks (the rank axis is a sequential
    grid dim; winner arrays accumulate in the revisited output blocks — see
    kernels/sgrid.py), so the caller needs no per-chunk host loop and no
    (n_l,T,n′) ``sep_found`` tensor ever exists in HBM.

    Returns (t_loc (n_l,n′) int32 — min separating launch-local rank,
    ``sgrid.SENTINEL`` when none; s_win (n_l,n′,ℓ) int32 — the set at that
    rank). Identical winners to ``levels._winners`` over the same chunk.
    """
    n_l, t_len, npr = mask.shape
    interpret = _interp(interpret)
    tb = 8
    n_pad = _ceil_mult(max(n_l, LANE), LANE)
    t_pad = _ceil_mult(max(t_len, tb), tb)

    def lane_layout(x, dtype):
        # (n_l, T, ...) → (..., T_pad, n_pad): rows on lanes, ranks on sublanes
        widths = [(0, n_pad - n_l), (0, t_pad - t_len)] + [(0, 0)] * (x.ndim - 2)
        x = jnp.pad(x.astype(dtype), widths)
        return jnp.transpose(x, tuple(range(2, x.ndim)) + (1, 0))

    twin, swin = _sgrid.sgrid_kernel(
        lane_layout(m2, jnp.float32), lane_layout(ci_s, jnp.float32),
        lane_layout(cj_s, jnp.float32), lane_layout(cij, jnp.float32),
        lane_layout(mask, jnp.uint8), lane_layout(s_ids, jnp.int32),
        tau, ell=ell, npr=npr, tb=tb, interpret=interpret,
    )
    t_loc = twin.T[:n_l]                                        # (n_l, n′)
    s_win = swin.reshape(npr, ell, n_pad).transpose(2, 0, 1)[:n_l]
    return t_loc, s_win


def _grid_winners(t_loc, s_win, t0):
    """Launch-local winners → the (t_win, removed_slot, s_win) triple in the
    rank dtype that levels' commit layer consumes. The kernel tracks int32
    launch-local offsets; the launch base t0 is added back here, so the
    kernel stays int32-clean even under x64 ranks."""
    from repro.core import levels as L

    found = t_loc < _sgrid.SENTINEL
    t_win = jnp.where(
        found, t0 + t_loc.astype(L._rank_dtype()), jnp.asarray(L._imax(), L._rank_dtype())
    )
    return t_win, found, s_win


def chunk_s_grid_tests(c, adj, compact, counts, rows, t0, tau, *, ell, n_chunk, n_max):
    """Tests half of the grid engine for a (possibly sharded) row block:
    gather ranks [t0, t0+n_chunk) (levels.gather_s — the SAME prologue every
    engine uses) and sweep them in one grid-resident kernel launch.
    Returns (t_win (n_l,n′), removed_slot (n_l,n′) bool, s_win (n_l,n′,ℓ))
    — the chunk_s_tests contract. Traceable (jit'd by its callers)."""
    from repro.core import levels as L

    ranks = t0 + jnp.arange(n_chunk, dtype=L._rank_dtype())
    m2, ci_s, cj_s, cij, mask, s_ids = L.gather_s(
        c, adj, compact, counts, rows, ranks, ell=ell, n_max=n_max
    )
    t_loc, s_win = ci_shared_grid(m2, ci_s, cj_s, cij, mask, s_ids, tau, ell=ell)
    return _grid_winners(t_loc, s_win, t0)


def chunk_s_grid_tests_cols(c_rows, c_cols, col_pos, adj, compact, counts,
                            rows, t0, tau, *, ell, n_chunk, n_max):
    """chunk_s_grid_tests for the ROW-SHARDED C layout (levels.gather_s_cols
    prologue — bit-identical gathered values, see tests/test_sharding.py)."""
    from repro.core import levels as L

    ranks = t0 + jnp.arange(n_chunk, dtype=L._rank_dtype())
    m2, ci_s, cj_s, cij, mask, s_ids = L.gather_s_cols(
        c_rows, c_cols, col_pos, adj, compact, counts, rows, ranks,
        ell=ell, n_max=n_max,
    )
    t_loc, s_win = ci_shared_grid(m2, ci_s, cj_s, cij, mask, s_ids, tau, ell=ell)
    return _grid_winners(t_loc, s_win, t0)


@functools.partial(jax.jit, static_argnames=("ell", "n_chunk", "n_max"))
def chunk_s_grid(c, adj, sep, compact, counts, t0, tau, *, ell, n_chunk, n_max):
    """Same contract as core.levels.chunk_s, but the whole rank range
    [t0, t0+n_chunk) runs as ONE grid-resident kernel launch with the
    commit fused into the same jitted program — one host dispatch per
    launch, usually one per level (engines "S-grid"; planned by
    levels.plan_level_grid so n_chunk depends only on static shapes)."""
    from repro.core import levels as L

    n = compact.shape[0]
    rows = jnp.arange(n, dtype=jnp.int32)
    t_win, removed_slot, s_win = chunk_s_grid_tests(
        c, adj, compact, counts, rows, t0, tau, ell=ell, n_chunk=n_chunk, n_max=n_max
    )
    return L._global_commit(adj, sep, compact, rows, t_win, removed_slot, s_win, ell)


# ------------------------------------- kernel-backed drop-in for levels.chunk_s
def _kernel_tests(c, adj, compact, counts, rows, ranks, tau, *, ell, n_max):
    """levels._tests_s with the per-set inverse + CI sweep in the Pallas
    kernels (the unrank/gather/mask prologue is the SAME levels.gather_s the
    jnp engine uses — gathers stay in XLA, which excels at them, and the
    masking semantics can't diverge across engines). Returns (sep_found
    (n_l,T,npr) bool, s_ids (n_l,T,ell))."""
    from repro.core import levels as L

    n_l, npr = compact.shape
    n_chunk = ranks.shape[0]
    m2, ci_s, cj_s, cij, mask, s_ids = L.gather_s(
        c, adj, compact, counts, rows, ranks, ell=ell, n_max=n_max
    )
    bsz = n_l * n_chunk
    sep_found = ci_shared(
        m2.reshape(bsz, ell, ell), ci_s.reshape(bsz, ell),
        cj_s.reshape(bsz, npr, ell), cij.reshape(bsz, npr),
        mask.reshape(bsz, npr), tau, ell=ell,
    ).reshape(n_l, n_chunk, npr)
    return sep_found, s_ids


@functools.partial(jax.jit, static_argnames=("ell", "n_chunk", "n_max"))
def chunk_s_kernel(c, adj, sep, compact, counts, t0, tau, *, ell, n_chunk, n_max):
    """Same contract as core.levels.chunk_s but the per-set inverse + CI sweep
    run in the Pallas kernels (:func:`_kernel_tests`)."""
    from repro.core import levels as L

    n = compact.shape[0]
    rows = jnp.arange(n, dtype=jnp.int32)
    ranks = t0 + jnp.arange(n_chunk, dtype=L._rank_dtype())
    sep_found, s_ids = _kernel_tests(c, adj, compact, counts, rows, ranks, tau,
                                     ell=ell, n_max=n_max)
    return L._commit(c, adj, sep, compact, counts, sep_found, ranks, s_ids, None, ell)


@functools.partial(jax.jit, static_argnames=("ell", "n_chunk", "n_max"))
def chunk_s_kernel_class(c, adj, sep, key, rows, t0, tau, *, ell, n_chunk, n_max):
    """One degree class's program of an S-kernel level ≥ 2
    (core.levels.run_level_classes): compact the class's rows (global ids,
    -1 padded) of the level-start ``adj`` to n′ = n_max slots, test ranks
    [t0, t0+n_chunk) as :func:`chunk_s_kernel` does, and fold the winners
    into the level's carried (sep, key) claims (levels.claim_rows). Nothing
    is removed until levels.commit_claims."""
    from repro.core import levels as L
    from repro.core.compact import compact_rows

    live = rows >= 0
    rows = jnp.maximum(rows, 0)
    compact, counts = compact_rows(adj[rows] & live[:, None], n_prime=n_max)
    ranks = t0 + jnp.arange(n_chunk, dtype=L._rank_dtype())
    sep_found, s_ids = _kernel_tests(c, adj, compact, counts, rows, ranks, tau,
                                     ell=ell, n_max=n_max)
    t_win, removed_slot, s_win = L._winners(sep_found, ranks, s_ids, None)
    return L.claim_rows(sep, key, compact, rows, t_win, removed_slot, s_win, ell)
