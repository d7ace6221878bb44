"""Batched per-level CI-test engines: the TPU re-formulation of cuPC-E / cuPC-S.

CUDA cuPC assigns *threads* to (edge × combo-slice) [cuPC-E] or to
conditioning sets S [cuPC-S]. On TPU we build the same two engines as dense
batched worklists:

  * ``level0``      — one fused elementwise pass over C (paper Alg. 3).
  * ``chunk_s``     — cuPC-S: for every (row i, combo-rank t) cell, gather
                      M2 = C[S,S] once, invert once (batched Cholesky), and
                      sweep *all* neighbours j of i with MXU-friendly einsums
                      — the paper's "share the pseudo-inverse locally" idea.
  * ``chunk_e``     — cuPC-E: for every (row i, neighbour slot p, rank t)
                      cell an independent CI test (no sharing) — the paper's
                      edge-major engine, kept for fidelity + benchmarks.

Early termination (paper §4.1) becomes *chunking*: ranks are processed in
host-looped chunks; edges removed by an earlier chunk mask out of later
chunks (the `alive` snapshot), and rows with n'_i < ℓ+1 are masked wholesale.
Level-1 never builds M2 at all: ρ(i,j|k) has a closed form (beyond-paper
optimisation; Fig. 6 shows ℓ=1 dominates runtime).

SepSet determinism: within a level the winning separating set for an edge is
the (endpoint-row, rank)-lexicographic minimum *per chunk*; across chunks the
first separating chunk wins. Because ranks ascend across chunks, this equals
the whole-level lexicographic minimum — the dense ℓ=1 kernel commit
(``commit_dense_l1``) reproduces it exactly. This is a deterministic
refinement of the paper's "whichever thread wins the race" and — like the
paper — does not affect the skeleton (PC-stable order-independence).

Engine-selection matrix (registry + dispatch live in core/engines.py; this
module owns the jnp engines, the chunk planner and the commit layer):

  engine     ℓ=1                     ℓ≥2                  backend
  ─────────  ──────────────────────  ───────────────────  ─────────────────────
  S          chunk_s                 chunk_s              any (XLA einsums)
  E          chunk_e                 chunk_e              any (XLA einsums)
  S-kernel   ops.chunk_s_kernel      run_level_classes    Pallas (interp off-TPU)
  S-grid     ops.chunk_s_grid        ops.chunk_s_grid     Pallas (interp off-TPU)
  L1-dense   ops.level1_dense        (resolves to S)      Pallas (interp off-TPU)
  auto       L1-dense                S-kernel             Pallas (interp off-TPU)

Chunk planning (``plan_level``): n′ (max row degree) is bucketed up to the
next power of two below one lane, then to lane (128) multiples, and the
rank-chunk length is a power of two derived from a VMEM-aware cell budget.
Both static shapes therefore recur across levels and runs instead of
retriggering one XLA/Mosaic compile per exact max-degree — level boundaries
reuse the jit cache (probed by tests/test_engines.py).

Degree classes (``plan_classes`` / ``run_level_classes``, the S-kernel route
at ℓ ≥ 2): one level-wide plan launches every row at the largest degree's
rank range and width, so a few hubs set the work of all n rows. The class
planner groups the rows by their own n′ bucket instead, and each class runs
only its own ranks and slots; the claims merge and commit once per level.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

from .cit import fisher_z
from .combinadics import binom_table

#: f32 contractions of the CI math run at full f32 precision: at the TPU's
#: default precision a matmul rounds its operands to bf16 (~3 digits),
#: enough to flip Fisher-z decisions near τ against the f32 Pallas kernels
#: and the float64 oracle. On the CPU this changes no bit.
_HI = jax.lax.Precision.HIGHEST

def _rank_dtype():
    """int64 ranks when x64 is on; int32 otherwise. C(n',l) beyond 2^29
    requires jax_enable_x64 (``JAX_ENABLE_X64=1``)."""
    return jnp.int64 if jax.config.jax_enable_x64 else jnp.int32


def _imax():
    return int(jnp.iinfo(_rank_dtype()).max) // 4


def _jtable(n_max):
    return jnp.asarray(np.minimum(binom_table(n_max), _imax()).astype(np.int64),
                       dtype=_rank_dtype())


# --------------------------------------------------------------------------
# level 0
# --------------------------------------------------------------------------
@jax.jit
def level0(c: jax.Array, tau: float) -> jax.Array:
    """Paper Alg. 3: adjacency after unconditional tests, Z(C_ij) > tau."""
    n = c.shape[0]
    keep = fisher_z(c) > tau
    eye = jnp.eye(n, dtype=bool)
    return keep & ~eye


# --------------------------------------------------------------------------
# level 0, discrete G² (pairwise contingency tables; q = 1)
# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("r",))
def level0_g2(stats, alpha, *, r: int) -> jax.Array:
    """Unconditional discrete pass: adjacency after pairwise G² tests.

    stats: core/cit.DiscreteStats; r: static run-wide max arity (the code
    stride — dof uses the true per-variable arities). Keeps edge (i, j)
    when chi2.sf(G², dof) < α, mirroring level0's "dependent ⇒ keep".
    """
    from repro.kernels import gsq

    codes, arities = stats.codes, stats.arities
    m, n = codes.shape
    jc = codes[:, :, None] * r + codes[:, None, :]  # (m, n, n) joint codes
    g2 = gsq.gsq_ref(jc.reshape(m, n * n), r=r, q=1).reshape(n, n)
    dof = jnp.maximum(
        (arities[:, None] - 1) * (arities[None, :] - 1), 1
    ).astype(jnp.float32)
    pval = jax.scipy.special.gammaincc(dof / 2.0, jnp.maximum(g2, 0.0) / 2.0)
    keep = pval < alpha
    return keep & ~jnp.eye(n, dtype=bool)


# --------------------------------------------------------------------------
# dynamic-n combination unranking (vectorised Alg. 6 over worklists)
# --------------------------------------------------------------------------
def _unrank_dyn(t, n_dyn, n_max: int, ell: int, table):
    """t-th lex ℓ-subset of {0..n_dyn-1}; n_dyn traced, n_max static bound.

    t, n_dyn broadcast together; output (..., ell) int32 positions.
    Invalid ranks (t >= C(n_dyn, ell)) return clamped junk — callers mask.
    """
    t = t.astype(_rank_dtype())
    shape = jnp.broadcast_shapes(t.shape, jnp.shape(n_dyn))
    rem = jnp.broadcast_to(t, shape)
    n_dyn = jnp.broadcast_to(jnp.asarray(n_dyn, jnp.int32), shape)
    c = jnp.zeros(shape, jnp.int32)
    out = jnp.zeros(shape + (ell,), jnp.int32)

    def body(k, carry):
        rem, c, out = carry
        tail = jnp.clip(n_dyn - k - 1, 0, n_max)
        slot = jnp.clip(ell - c - 1, 0, ell + 1)
        cnt = table[tail, slot]
        open_ = (k < n_dyn) & (c < ell)
        take = open_ & (rem < cnt)
        out = jnp.where(
            (jax.nn.one_hot(jnp.where(take, c, ell), ell + 1, dtype=bool)[..., :ell]),
            jnp.int32(k),
            out,
        )
        rem = jnp.where(open_ & ~take, rem - cnt, rem)
        c = c + take.astype(jnp.int32)
        return rem, c, out

    _, _, out = jax.lax.fori_loop(0, n_max, body, (rem, c, out))
    return out


# --------------------------------------------------------------------------
# shared CI math
# --------------------------------------------------------------------------
#: Baseline Tikhonov jitter of every engine's SPD inverse. The serving
#: layer's degradation ladder (repro/serve) re-runs ill-conditioned graphs
#: with escalated multiples of this value before falling back to the
#: stable_ref oracle — see ci_sweep's ``jitter`` parameter.
DEFAULT_JITTER = 1e-8


def _inv_spd(m, jitter=DEFAULT_JITTER):
    """Batched SPD inverse with Tikhonov jitter. The ℓ=2 case — the bulk of
    every PC run's ℓ≥2 work — is solved in closed form (adjugate / det):
    one fused elementwise op over the batch instead of 10⁵s of tiny LAPACK
    factorisations, which dominate batched sweeps on CPU. Larger blocks go
    through LAPACK as before.

    The jitter is scaled by each block's mean diagonal magnitude, so the
    regularisation is RELATIVE to the block rather than an absolute 1e-8:
    a fixed jitter under- or over-regularises blocks whose scale differs
    from 1 and biases the partial correlations of near-singular S-blocks.
    For correlation inputs the diagonal is exactly 1, so the scale factor
    is 1 and results are unchanged bit-for-bit; an ill-conditioned
    correlation fixture is parity-tested against stable_ref in
    tests/test_core_pc.py. The Pallas kernels (cholinv, sgrid) apply the
    same diagonal-scaled rule."""
    eye = jnp.eye(m.shape[-1], dtype=m.dtype)
    diag_scale = jnp.mean(
        jnp.abs(jnp.diagonal(m, axis1=-2, axis2=-1)), axis=-1
    )[..., None, None]
    m = m + (jitter * diag_scale) * eye
    if m.shape[-1] == 2:
        a, b = m[..., 0, 0], m[..., 0, 1]
        c, d = m[..., 1, 0], m[..., 1, 1]
        det = a * d - b * c
        adj2 = jnp.stack(
            [jnp.stack([d, -b], axis=-1), jnp.stack([-c, a], axis=-1)], axis=-2
        )
        return adj2 / det[..., None, None]
    return jnp.linalg.inv(m)


# --------------------------------------------------------------------------
# cuPC-S chunk: set-major with shared inverse
# --------------------------------------------------------------------------
def plan_sets(compact, counts, ranks, *, ell: int, n_max: int, n: int):
    """Unrank one chunk's conditioning sets for a (possibly sharded) row
    block: (s_ids (n_l,T,ell) clipped to [0, n-1], valid_set (n_l,T)).

    Layout-independent half of the worklist prologue — shared verbatim by
    the dense-C gather (:func:`gather_s`) and the row-sharded column gather
    (:func:`gather_s_cols`) so the two C layouts can never diverge on which
    sets a rank denotes.
    """
    n_l, npr = compact.shape
    n_chunk = ranks.shape[0]
    table = _jtable(n_max)
    total = table[jnp.clip(counts, 0, n_max), ell]  # C(n'_i, ell) per row
    valid_set = ranks[None, :] < total[:, None]  # (n_l, T)

    # positions → variable ids of S             (n_l, T, ell)
    pos = _unrank_dyn(ranks[None, :], counts[:, None], npr, ell, table)
    pos = jnp.where(valid_set[..., None], pos, 0)
    s_ids = jnp.take_along_axis(compact, pos.reshape(n_l, -1), axis=1).reshape(n_l, n_chunk, ell)
    s_ids = jnp.clip(s_ids, 0, n - 1)  # padded slots are masked anyway
    return s_ids, valid_set


def _set_mask(adj, compact, rows, s_ids, valid_set, n):
    """Full validity mask (n_l,T,npr): rank in range, j ∉ S, edge alive.
    Single source of truth for BOTH C layouts (and the Pallas engine's
    host-side gathers) — divergence here breaks cross-engine parity."""
    j_ids = jnp.clip(compact, 0, n - 1)  # (n_l, npr)
    in_s = jnp.any(j_ids[:, None, :, None] == s_ids[:, :, None, :], axis=-1)
    alive = adj[rows[:, None], j_ids] & (compact >= 0)  # (n_l,npr) snapshot
    return valid_set[:, :, None] & ~in_s & alive[:, None, :]


def gather_s(c, adj, compact, counts, rows, ranks, *, ell: int, n_max: int):
    """Shared cuPC-S worklist prologue: unrank the conditioning sets and
    gather every array the CI math needs, with the full validity mask.

    c/adj are GLOBAL (n,n); compact/counts/rows are LOCAL (n_l rows, global
    ids in `rows`). Returns (m2 (n_l,T,ell,ell), ci_s (n_l,T,ell),
    cj_s (n_l,T,npr,ell), cij (n_l,T,npr), mask (n_l,T,npr),
    s_ids (n_l,T,ell)). Single source of truth for the rank-validity /
    j∈S / alive-snapshot masking — the jnp engine (_tests_s) and the Pallas
    engine (kernels/ops.chunk_s_kernel) must never diverge here or the
    bit-identical cross-engine parity breaks.
    """
    n = c.shape[0]
    n_l, npr = compact.shape
    n_chunk = ranks.shape[0]
    s_ids, valid_set = plan_sets(compact, counts, ranks, ell=ell, n_max=n_max, n=n)

    # the gathers index with the ℓ set members as the LEADING axis and move
    # it last afterwards: with ℓ minor, compiling one chunk program for a
    # v5e spent ~25 s on the C[j, S] gather (n=150, ℓ=3), with ℓ major ~3 s.
    s_t = jnp.moveaxis(s_ids, -1, 0)  # (ell, n_l, T)
    # M2 = C[S,S] — gathered ONCE per (row, set): the cuPC-S sharing.
    m2 = jnp.moveaxis(c[s_t[:, None], s_t[None, :]], (0, 1), (-2, -1))  # (n_l,T,ell,ell)
    ci_s = jnp.moveaxis(c[rows[None, :, None], s_t], 0, -1)  # (n_l,T,ell)
    j_ids = jnp.clip(compact, 0, n - 1)  # (n_l, npr)
    cj_s = jnp.moveaxis(c[j_ids[None, :, None, :], s_t[..., None]], 0, -1)  # (n_l,T,npr,ell)
    cij = jnp.broadcast_to(c[rows[:, None], j_ids][:, None, :], (n_l, n_chunk, npr))

    mask = _set_mask(adj, compact, rows, s_ids, valid_set, n)
    return m2, ci_s, cj_s, cij, mask, s_ids


def subset_cols(c_cols, positions):
    """Cache-aware companion of :func:`gather_s_cols`: slice an already
    gathered column block down to a shrunk candidate set WITHOUT re-gathering.

    C never changes during a run and the active candidate set (vertices of
    degree ≥ 1) only shrinks — across chunks within a level and across level
    boundaries alike. A block gathered once therefore stays valid as a
    superset forever: the next level's ``c_cols`` is a pure local column
    subset of the cached one, bit-identical to a fresh all-gather.

    c_cols:    (n_rows, k_old)  a previously gathered C[:, cols_old] block;
    positions: (k_new,) int     position of each new col id inside cols_old
               (``col_pos_old[cols_new]`` — the caller must have verified
               cols_new ⊆ cols_old, which degree monotonicity guarantees).
    Returns (n_rows, k_new) — exactly C[:, cols_new], zero collectives.
    The per-level cache lifecycle (invalidation = recompute cols from the
    fresh degree counts at each level boundary) lives in
    ``core/distributed.ColumnCache``.
    """
    return c_cols[:, positions]


def gather_s_cols(c_rows, c_cols, col_pos, adj, compact, counts, rows, ranks,
                  *, ell: int, n_max: int):
    """cuPC-S worklist prologue for the ROW-SHARDED C layout.

    Instead of the full (n,n) matrix, the caller supplies
      c_rows:  (n_l, n)  this shard's rows of C (C[rows, :]);
      c_cols:  (≥n, k)   the gathered active candidate columns C[:, cols]
               (an all-gather of each shard's local column slice — O(n·k),
               never O(n²) — or a cached/subset block: see
               :func:`subset_cols`, which yields bit-identical values);
      col_pos: (n,)      global id → its position in `cols` (undefined for
               ids outside `cols`; such ids only occur in masked cells).

    Every C value the CI math reads satisfies "row ∈ shard OR column ∈
    cols": C[S,S'] and C[j,S] come from c_cols (S ⊆ cols by construction —
    cols ⊇ every compacted neighbour id), C[i,S] and C[i,j] from c_rows.
    The gathered fp32 values are exactly the dense path's values, so the
    downstream sweep is bit-identical (asserted by tests/test_sharding.py).
    """
    n = adj.shape[0]
    n_l, npr = compact.shape
    n_chunk = ranks.shape[0]
    s_ids, valid_set = plan_sets(compact, counts, ranks, ell=ell, n_max=n_max, n=n)
    loc = jnp.arange(n_l, dtype=jnp.int32)

    s_pos = col_pos[s_ids]  # (n_l,T,ell) positions into the k gathered cols
    # ℓ-major gathers, as in gather_s
    s_t = jnp.moveaxis(s_ids, -1, 0)  # (ell, n_l, T)
    p_t = jnp.moveaxis(s_pos, -1, 0)
    m2 = jnp.moveaxis(c_cols[s_t[:, None], p_t[None, :]], (0, 1), (-2, -1))  # (n_l,T,ell,ell)
    ci_s = jnp.moveaxis(c_rows[loc[None, :, None], s_t], 0, -1)  # (n_l,T,ell)
    j_ids = jnp.clip(compact, 0, n - 1)  # (n_l, npr)
    cj_s = jnp.moveaxis(c_cols[j_ids[None, :, None, :], p_t[..., None]], 0, -1)  # (n_l,T,npr,ell)
    cij = jnp.broadcast_to(c_rows[loc[:, None], j_ids][:, None, :], (n_l, n_chunk, npr))

    mask = _set_mask(adj, compact, rows, s_ids, valid_set, n)
    return m2, ci_s, cj_s, cij, mask, s_ids


def ci_sweep(m2, ci_s, cj_s, cij, mask, tau, *, ell: int,
             jitter: float = DEFAULT_JITTER):
    """The cuPC-S CI math on a gathered chunk: per-set inverse + shared
    vectors, then the neighbour sweep as MXU einsums. Layout-independent —
    both gather prologues feed it the same fp32 values, so its output is
    bit-identical across the dense and row-sharded C layouts.

    ``jitter`` scales the Tikhonov regularisation of the per-set inverse
    (see :func:`_inv_spd`); the default reproduces every engine's baseline
    behaviour bit-for-bit. The serving layer escalates it for
    ill-conditioned graphs (repro/serve degradation ladder)."""
    if ell == 1:
        g = 1.0 / jnp.maximum(m2, 1e-8)  # scalar "inverse"
    else:
        g = _inv_spd(m2, jitter)
    u_i = jnp.einsum("ntab,ntb->nta", g, ci_s, precision=_HI)
    var_i = 1.0 - jnp.einsum("nta,nta->nt", ci_s, u_i, precision=_HI)
    num = cij - jnp.einsum("ntpl,ntl->ntp", cj_s, u_i, precision=_HI)
    gw = jnp.einsum("ntab,ntpb->ntpa", g, cj_s, precision=_HI)
    var_j = 1.0 - jnp.einsum("ntpa,ntpa->ntp", cj_s, gw, precision=_HI)
    rho = num / jnp.sqrt(jnp.maximum(var_i[..., None] * var_j, 1e-20))
    indep = fisher_z(rho) <= tau  # (n_l,T,npr)
    return indep & mask


def _tests_s(c, adj, compact, counts, rows, ranks, tau, *, ell: int, n_max: int,
             jitter: float = DEFAULT_JITTER):
    """cuPC-S CI tests for the given (possibly sharded) row block.

    Returns (sep_found (n_l,T,npr) bool, s_ids (n_l,T,ell)).
    """
    m2, ci_s, cj_s, cij, mask, s_ids = gather_s(
        c, adj, compact, counts, rows, ranks, ell=ell, n_max=n_max
    )
    return ci_sweep(m2, ci_s, cj_s, cij, mask, tau, ell=ell, jitter=jitter), s_ids


def _tests_s_cols(c_rows, c_cols, col_pos, adj, compact, counts, rows, ranks,
                  tau, *, ell: int, n_max: int):
    """cuPC-S CI tests reading the row-sharded C layout (see gather_s_cols).

    Returns (sep_found (n_l,T,npr) bool, s_ids (n_l,T,ell)).
    """
    m2, ci_s, cj_s, cij, mask, s_ids = gather_s_cols(
        c_rows, c_cols, col_pos, adj, compact, counts, rows, ranks,
        ell=ell, n_max=n_max,
    )
    return ci_sweep(m2, ci_s, cj_s, cij, mask, tau, ell=ell), s_ids


@functools.partial(jax.jit, static_argnames=("ell", "n_chunk", "n_max"))
def chunk_s(c, adj, sep, compact, counts, t0, tau, *, ell: int, n_chunk: int, n_max: int):
    """Process combo-ranks [t0, t0+n_chunk) of every row, cuPC-S style.

    c:(n,n) fp32 · adj:(n,n) bool · sep:(n,n,Lmax) int32 · compact:(n,npr)
    counts:(n,) — returns updated (adj, sep).
    """
    n = compact.shape[0]
    rows = jnp.arange(n, dtype=jnp.int32)
    ranks = t0 + jnp.arange(n_chunk, dtype=_rank_dtype())  # (T,)
    sep_found, s_ids = _tests_s(c, adj, compact, counts, rows, ranks, tau, ell=ell, n_max=n_max)
    return _commit(c, adj, sep, compact, counts, sep_found, ranks, s_ids, None, ell)


# --------------------------------------------------------------------------
# discrete G² chunk: set-major worklist over contingency tables
# --------------------------------------------------------------------------
@functools.partial(
    jax.jit, static_argnames=("ell", "n_chunk", "n_max", "r", "use_kernel")
)
def chunk_g2(stats, adj, sep, compact, counts, t0, alpha, *, ell: int,
             n_chunk: int, n_max: int, r: int, use_kernel: bool = False):
    """Process combo-ranks [t0, t0+n_chunk) of every row with the discrete
    G² test — the cuPC-S worklist shape with contingency tables in place
    of partial correlations.

    Same contract as :func:`chunk_s` with the sufficient-statistics pytree
    (core/cit.DiscreteStats) riding the C slot and α riding the tau slot:
    the set-unranking prologue (:func:`plan_sets`) and validity mask
    (:func:`_set_mask`) are shared VERBATIM with the Gaussian engines, so
    which (row, rank, slot) cell denotes which test can never diverge
    across test objects. Per cell: fold the conditioning configuration and
    the (i, j) codes into one joint code, histogram it over the samples
    (kernels/gsq.py — Pallas when ``use_kernel``, its bitwise-identical
    jnp reference otherwise), reduce to G², and decide independence in
    p-value space with the cell's own dof. The winner commit is the same
    deterministic (rank, endpoint-order) rule as every other engine.
    """
    from repro.kernels import gsq

    codes, arities = stats.codes, stats.arities
    n = adj.shape[0]
    mm = codes.shape[0]
    _, npr = compact.shape
    rows = jnp.arange(n, dtype=jnp.int32)
    ranks = t0 + jnp.arange(n_chunk, dtype=_rank_dtype())
    s_ids, valid_set = plan_sets(compact, counts, ranks, ell=ell,
                                 n_max=n_max, n=n)
    mask = _set_mask(adj, compact, rows, s_ids, valid_set, n)
    j_ids = jnp.clip(compact, 0, n - 1)

    q = r ** ell
    codes_s = codes[:, s_ids]  # (m, n, T, ell)
    cfg = jnp.zeros((mm, n, n_chunk), jnp.int32)
    for k in range(ell):
        cfg = cfg * r + codes_s[..., k]
    # jc = cfg·r² + x_i·r + x_j — the layout _g2_from_counts unpacks
    jc = (cfg[..., None] * r + codes[:, :, None, None]) * r \
        + codes[:, j_ids][:, :, None, :]  # (m, n, T, npr)

    fn = gsq.gsq_cells if use_kernel else gsq.gsq_ref
    g2 = fn(jc.reshape(mm, -1), r=r, q=q).reshape(n, n_chunk, npr)

    ar_s = arities[s_ids].astype(jnp.float32)  # (n, T, ell)
    dof_cfg = jnp.prod(ar_s, axis=-1) if ell else jnp.ones((n, n_chunk))
    dof = ((arities[rows] - 1).astype(jnp.float32)[:, None, None]
           * (arities[j_ids] - 1).astype(jnp.float32)[:, None, :]
           * dof_cfg[:, :, None])
    dof = jnp.maximum(dof, 1.0)
    pval = jax.scipy.special.gammaincc(dof / 2.0, jnp.maximum(g2, 0.0) / 2.0)
    indep = pval >= alpha  # boundary counts as independent (Z ≤ τ parity)
    sep_found = indep & mask
    return _commit(stats, adj, sep, compact, counts, sep_found, ranks,
                   s_ids, None, ell)


# --------------------------------------------------------------------------
# cuPC-E chunk: edge-major, no sharing (paper Alg. 4 faithful)
# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("ell", "n_chunk", "n_max"))
def chunk_e(c, adj, sep, compact, counts, t0, tau, *, ell: int, n_chunk: int, n_max: int):
    """Process combo-ranks [t0, t0+n_chunk) of every (row, neighbour-slot).

    Every (i, p, t) cell performs an independent CI test, building and
    inverting its own M2 — the paper's cuPC-E parallelisation (γ×β threads),
    without the pseudo-inverse sharing of cuPC-S.
    """
    n, npr = compact.shape
    table = _jtable(n_max)
    rows = jnp.arange(n, dtype=jnp.int32)
    ranks = t0 + jnp.arange(n_chunk, dtype=_rank_dtype())  # (T,)
    totals = table[jnp.clip(counts - 1, 0, n_max), ell]  # C(n'_i - 1, ell)
    valid_rank = ranks[None, None, :] < totals[:, None, None]  # (n,1,T)

    # combos exclude the target slot p: unrank from C(n'_i-1, ell), shift ≥ p
    p_slots = jnp.arange(npr, dtype=jnp.int32)  # (npr,)
    pos = _unrank_dyn(
        ranks[None, None, :], (counts - 1)[:, None, None], npr, ell, table
    )  # (n,1,T,ell) — positions in the p-removed row; broadcast over p then shift
    pos = jnp.broadcast_to(pos, (n, npr, n_chunk, ell))
    pos = pos + (pos >= p_slots[None, :, None, None]).astype(pos.dtype)
    pos = jnp.clip(pos, 0, npr - 1)

    s_ids = compact[rows[:, None, None, None], pos]  # (n,npr,T,ell)
    s_ids = jnp.clip(s_ids, 0, n - 1)

    j_ids = jnp.clip(compact, 0, n - 1)  # (n,npr)
    m2 = c[s_ids[..., :, None], s_ids[..., None, :]]  # (n,npr,T,ell,ell)
    if ell == 1:
        g = 1.0 / jnp.maximum(m2, 1e-8)
    else:
        g = _inv_spd(m2)
    ci_s = c[rows[:, None, None, None], s_ids]  # (n,npr,T,ell)
    cj_s = c[j_ids[:, :, None, None], s_ids]
    u_i = jnp.einsum("nptab,nptb->npta", g, ci_s, precision=_HI)
    var_i = 1.0 - jnp.einsum("npta,npta->npt", ci_s, u_i, precision=_HI)
    gw = jnp.einsum("nptab,nptb->npta", g, cj_s, precision=_HI)
    var_j = 1.0 - jnp.einsum("npta,npta->npt", cj_s, gw, precision=_HI)
    num = c[rows[:, None], j_ids][:, :, None] - jnp.einsum("npta,npta->npt", cj_s, u_i, precision=_HI)
    rho = num / jnp.sqrt(jnp.maximum(var_i * var_j, 1e-20))
    indep = fisher_z(rho) <= tau  # (n,npr,T)

    alive = adj[rows[:, None], j_ids] & (compact >= 0)  # (n,npr)
    p_valid = p_slots[None, :] < counts[:, None]
    mask = valid_rank & alive[:, :, None] & p_valid[:, :, None]
    sep_found = jnp.swapaxes(indep & mask, 1, 2)  # → (n,T,npr) to share commit
    s_ids_tp = jnp.swapaxes(s_ids, 1, 2)  # (n,T,npr,ell)
    return _commit(c, adj, sep, compact, counts, sep_found, ranks, None, s_ids_tp, ell)


# --------------------------------------------------------------------------
# commit: removals + deterministic sepset recording
# --------------------------------------------------------------------------
def _winners(sep_found, ranks, s_ids_shared, s_ids_per_edge):
    """Per-(row, slot) minimum separating rank within the chunk.

    sep_found: (n_l,T,npr) → (t_win (n_l,npr), removed_slot (n_l,npr) bool,
    s_win (n_l,npr,ell)). Row-local: safe to compute on a shard.
    """
    n_l, n_chunk, npr = sep_found.shape
    imax = _imax()
    rank_mat = jnp.where(sep_found, ranks[None, :, None], imax)  # (n_l,T,npr)
    t_win = jnp.min(rank_mat, axis=1)
    t_arg = jnp.argmin(rank_mat, axis=1)
    removed_slot = t_win < imax
    loc = jnp.arange(n_l, dtype=jnp.int32)
    if s_ids_shared is not None:
        s_win = s_ids_shared[loc[:, None], t_arg]  # (n_l,npr,ell)
    else:
        s_win = s_ids_per_edge[loc[:, None], t_arg, jnp.arange(npr)[None, :]]
    return t_win, removed_slot, s_win


def _slot_keys(rows, j_ids, t_win, removed_slot):
    """Row i's claim on edge (i, j) per slot: rank·2 + endpoint-order for
    winner slots, imax elsewhere — the one key every commit compares."""
    order_bit = (rows[:, None] > j_ids).astype(_rank_dtype())
    return jnp.where(removed_slot, t_win * 2 + order_bit, _imax())


def _use_own(key_own, key_other):
    """The endpoint tie-break of every commit: where both endpoints of an
    edge claim it, the sepset comes from the row whose key is the smaller,
    and from row i itself on a tie (keys differ in their endpoint-order
    bit, so a tie means neither claimed)."""
    return key_own <= key_other


def _commit_key_mat(compact_full, rows_full, t_win, removed_slot, n):
    """Scatter per-(row, slot) winner ranks into the dense (n, n) key matrix.

    key_mat[i, j] is row i's claim on edge (i, j) (:func:`_slot_keys`). The
    symmetric edge decision is then min(key_mat, key_mat.T) — shared by the
    replicated commit (:func:`_global_commit`) and the row-sharded sepset commit
    (:func:`commit_sep_rows`), so the two layouts cannot diverge on which
    endpoint's separating set wins. Returns (j_ids (n, npr), key_mat (n, n)).
    """
    j_ids = jnp.clip(compact_full, 0, n - 1)
    key = _slot_keys(rows_full, j_ids, t_win, removed_slot)
    key_mat = jnp.full((n, n), _imax(), dtype=_rank_dtype()).at[rows_full[:, None], j_ids].min(key)
    return j_ids, key_mat


def _global_commit(adj, sep, compact_full, rows_full, t_win, removed_slot, s_win, ell):
    """Apply removals + sepsets to the GLOBAL adj/sep given full-width winner
    arrays (t_win/removed_slot/s_win over all n rows, e.g. after all_gather).

    Deterministic winner per undirected edge: lexicographic min of
    (rank, endpoint-order) — see module docstring.
    """
    n = adj.shape[0]
    imax = _imax()
    j_ids, key_mat = _commit_key_mat(compact_full, rows_full, t_win, removed_slot, n)
    # sepset writes: ONLY winner slots may scatter — padded compact slots
    # clip onto column 0 and a last-writer-wins .set would stomp real
    # records with zeros (caught by test_sepsets_certify_removals).
    j_write = jnp.where(removed_slot, j_ids, n)  # losers → dump column n
    s_mat = (
        jnp.zeros((n, n + 1, ell), jnp.int32)
        .at[rows_full[:, None], j_write]
        .set(s_win)[:, :n]
    )
    final_key = jnp.minimum(key_mat, key_mat.T)
    newly_removed = final_key < imax  # (n,n) symmetric
    use_own = _use_own(key_mat, key_mat.T)
    s_final = jnp.where(use_own[..., None], s_mat, jnp.swapaxes(s_mat, 0, 1))

    adj_new = adj & ~newly_removed
    lmax = sep.shape[-1]
    write = (newly_removed & adj)[..., None]  # only edges alive until now
    sep_new = jnp.where(
        write & (jnp.arange(lmax) < ell)[None, None, :],
        jnp.pad(s_final, ((0, 0), (0, 0), (0, lmax - ell)), constant_values=-1),
        sep,
    )
    return adj_new, sep_new


def commit_adj(adj, key_mat):
    """The replicated half of the commit: symmetric edge removal from the
    dense winner-key matrix (adjacency symmetrization must see BOTH
    endpoints' claims, so it stays replicated even when the sepset tensor
    is row-sharded). Returns the updated (n, n) bool adjacency."""
    return adj & ~(jnp.minimum(key_mat, key_mat.T) < _imax())


def commit_sep_rows(sep_rows, row_ids, adj, key_mat, compact_full, removed_slot,
                    s_win, ell):
    """Row-shard-LOCAL sepset commit: update this shard's block of the
    (n, n, Lmax) sepset tensor from full-width winner arrays.

    The replicated commit (:func:`_global_commit`) scatters an O(n²·ℓ)
    s_mat on every device; when the sepset tensor is row-sharded
    (``pc_distributed(shard_sep=True)``) each device only needs the writes
    landing in ITS rows — O(n²·ℓ / n_dev) work and memory. Two claim
    sources feed a local row i:

      * row i's own winner slots (scattered by target column j), and
      * every other row j's winner slot targeting i (the transposed claim —
        scattered by (j_ids[j, p] → local row, source j)).

    The per-edge tie-break (:func:`_use_own`) is :func:`_global_commit`'s
    own, so the sharded and replicated layouts commit bit-identical sepsets
    (tests/test_sharding.py).

    sep_rows:     (n_l, n, Lmax) this shard's sepset rows;
    row_ids:      (n_l,) global row ids (ids ≥ n are shard padding — their
                  writes are masked; their stored junk is trimmed on gather);
    adj:          (n, n) PRE-commit adjacency (writes only hit edges alive
                  until now, as in the replicated commit);
    key_mat:      (n, n) from :func:`_commit_key_mat`;
    compact_full / removed_slot / s_win: full-width (n, npr[, ℓ]) winner
                  arrays (post all-gather).
    Returns the updated (n_l, n, Lmax) block.
    """
    n = adj.shape[0]
    n_l = sep_rows.shape[0]
    imax = _imax()
    rid = jnp.clip(row_ids, 0, n - 1)
    valid_row = row_ids < n
    key_own = key_mat[rid]  # (n_l, n): local rows' claims
    key_oth = key_mat.T[rid]  # (n_l, n): the other endpoints' claims
    use_own = _use_own(key_own, key_oth)
    newly_removed = jnp.minimum(key_own, key_oth) < imax

    # own claims: scatter local winner slots by target column (losers → dump
    # column n, same rule as the replicated commit's s_mat scatter)
    j_ids_l = jnp.clip(compact_full[rid], 0, n - 1)  # (n_l, npr)
    rem_l = removed_slot[rid]
    loc = jnp.arange(n_l, dtype=jnp.int32)
    j_write = jnp.where(rem_l, j_ids_l, n)
    s_own = (
        jnp.zeros((n_l, n + 1, ell), jnp.int32)
        .at[loc[:, None], j_write]
        .set(s_win[rid])[:, :n]
    )

    # transposed claims: global row g's winner slot p targets row
    # compact_full[g, p]; claims landing inside this shard scatter into
    # (target-local, g), everything else → dump row n_l
    j_ids_f = jnp.clip(compact_full, 0, n - 1)  # (n, npr)
    t_loc = j_ids_f - row_ids[0]
    in_shard = removed_slot & (t_loc >= 0) & (t_loc < n_l)
    t_loc = jnp.where(in_shard, t_loc, n_l)
    g = jnp.arange(compact_full.shape[0], dtype=jnp.int32)
    s_oth = (
        jnp.zeros((n_l + 1, n, ell), jnp.int32)
        .at[t_loc, jnp.broadcast_to(g[:, None], t_loc.shape)]
        .set(s_win)[:n_l]
    )

    s_final = jnp.where(use_own[..., None], s_own, s_oth)
    write = newly_removed & adj[rid] & valid_row[:, None]
    lmax = sep_rows.shape[-1]
    return jnp.where(
        write[..., None] & (jnp.arange(lmax) < ell)[None, None, :],
        jnp.pad(s_final, ((0, 0), (0, 0), (0, lmax - ell)), constant_values=-1),
        sep_rows,
    )


def _commit(c, adj, sep, compact, counts, sep_found, ranks, s_ids_shared, s_ids_per_edge, ell):
    """sep_found: (n,T,npr). Shared engines pass s_ids (n,T,ell); edge-major
    engines pass per-edge sets (n,T,npr,ell)."""
    n = adj.shape[0]
    rows = jnp.arange(n, dtype=jnp.int32)
    t_win, removed_slot, s_win = _winners(sep_found, ranks, s_ids_shared, s_ids_per_edge)
    return _global_commit(adj, sep, compact, rows, t_win, removed_slot, s_win, ell)


# --------------------------------------------------------------------------
# split tests/commit chunk functions (async dispatch pipelining)
# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("ell", "n_chunk", "n_max"))
def chunk_s_tests(c, adj, compact, counts, t0, tau, *, ell: int, n_chunk: int, n_max: int):
    """The tests half of :func:`chunk_s`: CI-test combo-ranks
    [t0, t0+n_chunk) and reduce to per-(row, slot) winner arrays, WITHOUT
    committing. Returns (t_win (n,npr), removed_slot (n,npr) bool,
    s_win (n,npr,ell)) — feed to :func:`chunk_s_commit`.

    Why the split is safe to pipeline: ``adj`` here is only an *alive
    snapshot* masking which cells may claim a removal. A stale snapshot
    (any adjacency between the level start and the latest commit) produces
    extra claims ONLY on already-removed edges — claims for still-alive
    edges are identical cell-for-cell — and :func:`chunk_s_commit` masks
    sepset writes with the chained pre-commit adjacency, so stale claims
    are discarded. Chunk t+1's tests therefore need not wait for chunk t's
    commit: results stay bit-identical for ANY dispatch-ahead depth
    (asserted by tests/test_sharding.py).
    """
    n = compact.shape[0]
    rows = jnp.arange(n, dtype=jnp.int32)
    ranks = t0 + jnp.arange(n_chunk, dtype=_rank_dtype())
    sep_found, s_ids = _tests_s(c, adj, compact, counts, rows, ranks, tau, ell=ell, n_max=n_max)
    return _winners(sep_found, ranks, s_ids, None)


@functools.partial(jax.jit, static_argnames=("ell",))
def chunk_s_commit(adj, sep, compact, t_win, removed_slot, s_win, *, ell: int):
    """The commit half of :func:`chunk_s`: apply one chunk's winner arrays
    (from :func:`chunk_s_tests`) to the chained (adj, sep) state. Commits
    MUST apply in ascending-rank chunk order — the first separating chunk
    wins (module docstring); the tests may run arbitrarily far ahead."""
    n = adj.shape[0]
    rows = jnp.arange(n, dtype=jnp.int32)
    return _global_commit(adj, sep, compact, rows, t_win, removed_slot, s_win, ell)


# --------------------------------------------------------------------------
# dense ℓ=1 commit (kernel-backed L1-dense engine)
# --------------------------------------------------------------------------
@jax.jit
def commit_dense_l1(adj, sep, kwin):
    """Commit the fused dense ℓ=1 kernel result (kernels/level1.py).

    kwin[i, j] is the minimum separating k restricted to adj(i) \\ {j} (or
    ≥ 2^30 when row i found none). Its rank inside row i's sorted neighbour
    list is exactly the combo-rank chunk_s would have found, so applying the
    same (rank·2 + endpoint-order) lexicographic-min rule per undirected
    edge yields sepsets bit-identical to the chunked S engine.
    """
    n = adj.shape[0]
    imax = _imax()
    rd = _rank_dtype()
    adji = adj.astype(rd)
    prefix = jnp.cumsum(adji, axis=1) - adji  # exclusive: rank of id k in row
    kwin_c = jnp.clip(kwin, 0, n - 1).astype(jnp.int32)
    rank = jnp.take_along_axis(prefix, kwin_c, axis=1)  # (n,n): rank of kwin[i,j]
    rows = jnp.arange(n, dtype=jnp.int32)
    order_bit = (rows[:, None] > rows[None, :]).astype(rd)
    own = (kwin < jnp.asarray(2**30, kwin.dtype)) & adj
    key = jnp.where(own, rank * 2 + order_bit, imax)
    final_key = jnp.minimum(key, key.T)
    newly_removed = (final_key < imax) & adj
    use_own = key <= key.T
    s_win = jnp.where(use_own, kwin_c, kwin_c.T)
    adj_new = adj & ~newly_removed
    sep_new = sep.at[:, :, 0].set(jnp.where(newly_removed, s_win, sep[:, :, 0]))
    return adj_new, sep_new


# --------------------------------------------------------------------------
# chunk planning: bucketed static shapes shared by jnp and kernel engines
# --------------------------------------------------------------------------
#: Cells (worklist entries) a single device dispatch may materialise —
#: shared default of every engine (jnp, kernel, sharded). Derivation: one
#: chunk's dominant array is the (n·T, n′, ℓ) fp32 gather — 2^24 cells
#: ≈ 64 MB in HBM, far under one chip's HBM while big enough to amortise
#: dispatch overhead; the Pallas kernels stream it through fixed (8, 128)
#: VMEM tiles (ℓ²·4 KB per tile ≪ 16 MB VMEM), so the same budget is safe
#: for the jnp and kernel engines alike.
DEFAULT_CELL_BUDGET = 2**24

#: Per-LAUNCH cell budget of the grid-resident engine ("S-grid"): the rank
#: axis streams through the kernel grid, so a launch materialises only the
#: XLA gather (no (n·T, n′) sep_found tensor, no SoA copies, no per-chunk
#: winner round-trips) — 4× the chunked per-dispatch budget covers a whole
#: level in one host dispatch for every tracked workload while staying
#: within the same HBM envelope the chunked engines used to spend on
#: gather + intermediates.
GRID_CELL_BUDGET = 2**26


def _check_rank_capacity(total: int, n_chunk: int, ell: int):
    """Satellite guard for the int32-rank regime: combo ranks are carried in
    :func:`_rank_dtype` and committed as keys ``rank·2 + bit``, so every
    rank a chunk can touch (≤ total + n_chunk) must stay below
    :func:`_imax`. Without this guard, C(n′, ℓ) past the dtype capacity
    silently ALIASES ranks through the clipped binomial table
    (core/combinadics.py) instead of failing. Returns a (possibly reduced)
    n_chunk; raises when the level itself is unrepresentable.

    The bound is ``imax // 2``, not ``imax``: the commit path compares keys
    ``rank·2 + bit`` against the ``imax`` sentinel (``final_key < imax``
    decides removal), so a level is only representable while its *doubled*
    worst rank stays under the sentinel — a rank in (imax/2, imax) would
    trace fine but silently never commit its winner."""
    imax = _imax()
    if total > imax // 2:
        raise ValueError(
            f"level with {total} conditioning sets (ell={ell}) exceeds the "
            f"rank capacity of {_rank_dtype().dtype.name}: the commit-key "
            f"capacity is {imax // 2} (keys are rank*2+bit vs the {imax} "
            "sentinel); "
            "rerun with JAX_ENABLE_X64=1 and engine='S' for int64 ranks "
            "(the Pallas kernels do not compile for the TPU under x64), "
            "or cap max_level"
        )
    while n_chunk > 1 and total + n_chunk > imax:
        n_chunk //= 2
    return n_chunk


def _pow2_ceil(x: int) -> int:
    return 1 if x <= 1 else 1 << (x - 1).bit_length()


def _pow2_floor(x: int) -> int:
    return 1 if x <= 1 else 1 << (x.bit_length() - 1)


def bucket_npr(npr: int, lane: int = 128) -> int:
    """Round the compacted width n′ up to the next power of two (below one
    lane) or lane multiple (at/above), so level boundaries reuse compiled
    chunk functions instead of one fresh compile per exact max-degree."""
    if npr <= 1:
        return npr
    return _pow2_ceil(npr) if npr < lane else -(-npr // lane) * lane


def plan_level(
    npr: int,
    ell: int,
    n_rows: int,
    engine: str = "S",
    cell_budget: int = DEFAULT_CELL_BUDGET,
    bucket: bool = True,
    n_cols: int | None = None,
):
    """Plan one level's static shapes: (npr_bucket, n_chunk, total_ranks).

    ``cell_budget`` bounds the dominant worklist's cell count per dispatch —
    shared by the jnp engines and the Pallas chunk_s_kernel (whose biggest
    live array, the (n·T, n′, ℓ) neighbour gather, has the same cell count;
    its per-tile VMEM footprint is a fixed ℓ²·8·128 fp32 regardless of T).
    With ``bucket`` the chunk length is a power of two and ranks beyond
    ``total`` are masked by the engines' valid_set/valid_rank logic, so the
    (ℓ, n_chunk, n′) jit key recurs across levels; bucket=False reproduces
    the legacy exact-shape behaviour (one compile per distinct max-degree).
    ``n_cols`` (the global variable count) caps the bucket — a compact row
    can never be wider than n, so buckets beyond it would misstate the
    built shapes and shrink n_chunk below budget for nothing.
    """
    npr_b = bucket_npr(npr) if bucket else npr
    if n_cols is not None:
        npr_b = min(npr_b, n_cols)
    if engine.upper() == "S":
        total = math.comb(npr, ell)
        per_rank_cells = n_rows * npr_b * max(ell, 1) * max(ell, 1)
    else:
        total = math.comb(max(npr - 1, 0), ell)
        per_rank_cells = n_rows * npr_b * max(ell, 1) * max(ell, 1) * npr_b
    budget_chunk = max(1, cell_budget // max(per_rank_cells, 1))
    if bucket:
        n_chunk = min(_pow2_ceil(total), _pow2_floor(budget_chunk))
    else:
        n_chunk = max(1, min(total, budget_chunk))
    return npr_b, _check_rank_capacity(total, n_chunk, ell), total


# --------------------------------------------------------------------------
# host-side level driver
# --------------------------------------------------------------------------
def run_level(
    c,
    adj,
    sep,
    ell: int,
    tau: float,
    engine: str = "S",
    cell_budget: int = DEFAULT_CELL_BUDGET,
    chunk_fn_s=None,
    chunk_fn_e=None,
    bucket: bool = True,
    pipeline_depth: int = 1,
):
    """Run one PC-stable level. Host loop over rank-chunks (early-termination
    re-compaction happens implicitly through the `alive` snapshot).

    engine ∈ {"S", "E"} selects the jnp worklist shape; kernel-backed chunk
    functions slot in via chunk_fn_s/chunk_fn_e (see core/engines.py for the
    public registry). Returns (adj, sep, stats-dict); stats["dispatches"]
    counts the host-dispatched device programs the level issued (fused
    chunks count 1 each, split tests+commit pairs count 2).

    pipeline_depth ≥ 2 splits each chunk into tests + commit
    (:func:`chunk_s_tests` / :func:`chunk_s_commit`) and keeps up to that
    many chunks' tests in flight before the oldest commit is applied —
    chunk t+1's gather/unrank no longer serialises behind chunk t's commit
    in the XLA dependency graph (the tests read an alive snapshot that may
    lag the commits by up to depth−1 chunks, which cannot change results —
    see chunk_s_tests). Bit-identical to the sync path for any depth; only
    the jnp "S" worklist pipelines (kernel-backed chunk functions are fused
    tests+commit programs and run depth-1).
    """
    from collections import deque

    from .compact import compact_rows

    # adj (not c) owns the variable count: the c slot may carry a non-array
    # sufficient-statistics pytree (e.g. cit.DiscreteStats for chunk_g2)
    n = adj.shape[0]
    tracer = obs.current()
    with tracer.span("plan", level=ell):
        counts_host = np.asarray(obs.fetch(jnp.sum(adj, axis=1), site="levels.plan"))
        npr = int(counts_host.max(initial=0))
        if npr - 1 < ell:
            return adj, sep, {"skipped": True, "chunks": 0, "dispatches": 0,
                              "npr": npr, "engine": engine}
        npr_b, n_chunk, total = plan_level(
            npr, ell, n, engine=engine, cell_budget=cell_budget, bucket=bucket, n_cols=n
        )
        compact, counts = compact_rows(adj, n_prime=npr_b)
    depth = max(1, pipeline_depth)
    pipelined = depth > 1 and engine.upper() == "S" and chunk_fn_s is None

    chunks = 0
    if pipelined:
        pending: deque = deque()
        for t0 in range(0, total, n_chunk):
            with tracer.span("chunk", t0=t0):
                pending.append(chunk_s_tests(
                    c, adj, compact, counts, jnp.asarray(t0, _rank_dtype()), tau,
                    ell=ell, n_chunk=n_chunk, n_max=npr_b,
                ))
                chunks += 1
                if len(pending) >= depth:
                    adj, sep = chunk_s_commit(adj, sep, compact, *pending.popleft(), ell=ell)
        while pending:
            adj, sep = chunk_s_commit(adj, sep, compact, *pending.popleft(), ell=ell)
    else:
        fn = (chunk_fn_s or chunk_s) if engine.upper() == "S" else (chunk_fn_e or chunk_e)
        for t0 in range(0, total, n_chunk):
            with tracer.span("chunk", t0=t0):
                adj, sep = fn(
                    c, adj, sep, compact, counts, jnp.asarray(t0, _rank_dtype()), tau,
                    ell=ell, n_chunk=n_chunk, n_max=npr_b,
                )
            chunks += 1
    return adj, sep, {
        "skipped": False, "chunks": chunks, "npr": npr, "npr_bucket": npr_b,
        "n_chunk": n_chunk, "total_sets": total, "engine": engine,
        "compile_key": (ell, n_chunk, npr_b),
        "pipeline_depth": depth if pipelined else 1,
        "dispatches": chunks * (2 if pipelined else 1),
    }


# --------------------------------------------------------------------------
# degree-class worklists (the S-kernel route at ℓ ≥ 2)
# --------------------------------------------------------------------------
#: Fewest rows a class launches: one sublane tile of the f32 gathers. The
#: Pallas sweep flattens rows × ranks into its lane axis, so a small class
#: (a handful of hubs) costs only its own rows.
CLASS_ROW_FLOOR = 8


@dataclass(frozen=True)
class DegreeClass:
    """One worklist of a level: the rows whose degree shares an n′ bucket.

    rows: (rows_bucket,) global row ids, -1 padded; bucket: the class's slot
    width n′ (its largest degree where planned without bucketing) and its
    jit key's n_max; n_chunk: ranks per program; total:
    C(max degree in the class, ℓ), the class's rank range."""

    rows: np.ndarray
    bucket: int
    n_chunk: int
    total: int

    @property
    def chunks(self) -> int:
        return -(-self.total // self.n_chunk)

    @property
    def launched(self) -> int:
        """(row, set, neighbour) cells the class's programs launch."""
        return self.rows.size * self.chunks * self.n_chunk * self.bucket


def plan_classes(counts: np.ndarray, ell: int,
                 cell_budget: int = DEFAULT_CELL_BUDGET,
                 bucket: bool = True) -> list[DegreeClass]:
    """Partition a level's rows into degree classes (host side).

    Rows with d_i ≤ ℓ hold no ℓ-set beside a neighbour and are dropped; the
    rest group by ``bucket_npr(d_i)``. Each class pads its rows to a power
    of two (at least :data:`CLASS_ROW_FLOOR`, at most n) and takes its
    chunk length from :func:`plan_level` at its own row count, width and
    rank range, so the jit keys recur across levels and graphs. Where the
    classes would launch more cells than one class of all n rows at the
    level-wide plan (power-of-two rounding on a near-uniform graph), that
    one class is returned: no graph launches more than the uniform plan.
    ``bucket=False`` gives each class :func:`plan_level`'s exact width and
    chunk length (its largest degree) in place of the bucketed ones.
    """
    n = counts.shape[0]
    uniform = DegreeClass(np.arange(n, dtype=np.int32), *plan_level(
        int(counts.max()), ell, n, cell_budget=cell_budget, bucket=bucket, n_cols=n))
    live = np.flatnonzero(counts > ell)
    buckets = np.array([bucket_npr(int(d)) for d in counts[live]])
    classes = []
    for b in np.unique(buckets):
        rows = live[buckets == b].astype(np.int32)
        rows_b = min(max(CLASS_ROW_FLOOR, _pow2_ceil(rows.size)), n)
        classes.append(DegreeClass(
            np.pad(rows, (0, rows_b - rows.size), constant_values=-1),
            *plan_level(int(counts[rows].max()), ell, rows_b, cell_budget=cell_budget,
                        bucket=bucket, n_cols=n)))
    if sum(k.launched for k in classes) > uniform.launched:
        return [uniform]
    return classes


def claim_rows(sep, key, compact, rows, t_win, removed_slot, s_win, ell: int):
    """Fold one class program's per-(row, slot) winners into the level's
    carried claims: ``key`` (n, n) holds each row's least claim on each
    edge so far, and ``sep[i, j, :ℓ]`` row i's set at that claim (an edge
    alive at the level's start holds -1 in every slot, so the sepset tensor
    carries the claims; :func:`commit_claims` settles both endpoints').

    Within a class ranks ascend across programs and classes are
    row-disjoint, so a (row, j) set is written only where no earlier
    program claimed it. ``rows`` are global ids; padded rows claim nothing.
    """
    n = key.shape[0]
    j_ids = jnp.clip(compact, 0, n - 1)
    new = _slot_keys(rows, j_ids, t_win, removed_slot).astype(key.dtype)
    first = new < key[rows[:, None], j_ids]
    key = key.at[rows[:, None], j_ids].min(new)
    j_write = jnp.where(first, j_ids, n)  # others → out of range, dropped
    s_pad = jnp.pad(s_win, ((0, 0), (0, 0), (0, sep.shape[-1] - ell)), constant_values=-1)
    sep = sep.at[rows[:, None], j_write].set(s_pad, mode="drop")
    return sep, key


@jax.jit
def commit_claims(adj, sep, key):
    """Commit a level's merged claims at once: each edge goes by the least
    of its two endpoints' keys (the whole-level (rank, endpoint-order)
    minimum, which the rank-ascending chunks of :func:`run_level` reach
    too), and takes that endpoint's set."""
    use_own = _use_own(key, key.T)[..., None]
    return commit_adj(adj, key), jnp.where(use_own, sep, jnp.swapaxes(sep, 0, 1))


def run_level_classes(c, adj, sep, ell: int, tau,
                      cell_budget: int = DEFAULT_CELL_BUDGET, bucket: bool = True):
    """One PC-stable level of the S-kernel engine as degree-class worklists
    (:func:`plan_classes`).

    Each program (kernels/ops.chunk_s_kernel_class) tests ranks
    [t0, t0+n_chunk) of a class's rows against the level-start adjacency
    and folds the winners in with :func:`claim_rows`; :func:`commit_claims`
    then applies the level. Skeleton and sepsets are bit-identical to
    :func:`run_level`. Same return contract; stats carry ``classes`` and
    ``launched_tests`` in place of the level-wide ``n_chunk`` /
    ``npr_bucket``; ``bucket`` as in :func:`plan_classes`.
    """
    from repro.kernels.ops import chunk_s_kernel_class

    n = adj.shape[0]
    tracer = obs.current()
    with tracer.span("plan", level=ell):
        counts_host = np.asarray(obs.fetch(jnp.sum(adj, axis=1), site="levels.plan"))
        npr = int(counts_host.max(initial=0))
        if npr - 1 < ell:
            return adj, sep, {"skipped": True, "chunks": 0, "dispatches": 0,
                              "npr": npr}
        classes = plan_classes(counts_host, ell, cell_budget=cell_budget, bucket=bucket)
        rows = [jnp.asarray(k.rows) for k in classes]
        key = jnp.full((n, n), _imax(), _rank_dtype())
    for k, rows_k in zip(classes, rows):
        for t0 in range(0, k.total, k.n_chunk):
            with tracer.span("chunk", cls=k.bucket, t0=t0):
                sep, key = chunk_s_kernel_class(
                    c, adj, sep, key, rows_k, jnp.asarray(t0, _rank_dtype()), tau,
                    ell=ell, n_chunk=k.n_chunk, n_max=k.bucket)
    adj, sep = commit_claims(adj, sep, key)
    chunks = sum(k.chunks for k in classes)
    return adj, sep, {
        "skipped": False, "chunks": chunks, "npr": npr,
        "total_sets": math.comb(npr, ell),
        "classes": [{"bucket": k.bucket, "rows": int((k.rows >= 0).sum()),
                     "rows_bucket": k.rows.size, "n_chunk": k.n_chunk,
                     "total": k.total, "chunks": k.chunks} for k in classes],
        "launched_tests": sum(k.launched for k in classes),
        "dispatches": chunks + 1,
    }
