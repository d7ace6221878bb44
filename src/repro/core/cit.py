"""Conditional-independence testing — the pluggable ``CITest`` seam.

The constraint-based skeleton phase is test-agnostic (ParallelPC, arXiv
1510.03042): the level loop, worklists and sepset commit compose with ANY
decision rule "is Vi ⟂ Vj | S?". This module owns that seam:

  * the Gaussian partial-correlation machinery the paper specialises every
    kernel to (§4.3–4.4) — module-level functions, unchanged contracts;
  * the :class:`CITest` protocol + its two instances,
    :class:`GaussianCITest` (sufficient statistic: the correlation matrix;
    per-level scalar: the Fisher-z threshold τ) and :class:`DiscreteCITest`
    (sufficient statistic: integer level codes + arities; per-level
    scalar: α itself — the decision happens in p-value space,
    ``chi2.sf(G², dof) ≥ α``, with dof-aware thresholds per worklist cell).

Gaussian math (paper Eq. 4–7): all tests reduce to partial correlations
computed from the global correlation matrix C:

    ρ(Vi, Vj | S)  via  H = M0 − M1 · M2⁻¹ · M1ᵀ          (Eq. 4–5)
    Z(ρ) = |atanh ρ|  compared against  τ = Φ⁻¹(1−α/2)/√(m−|S|−3)   (Eq. 6–7)

M2 = C[S,S] may be ill-conditioned; the paper uses a Moore–Penrose
pseudo-inverse built from a Cholesky factorisation (Alg. 7, Courrieu).
We provide both the paper-faithful pseudo-inverse and a fast
Cholesky-solve path with Tikhonov jitter; they agree on well-conditioned
inputs (tested) and the pinv path is used when `robust=True`.

Discrete math: G² = 2 Σ_abc N_abc·log(N_abc·N_++c / (N_a+c·N_+bc)) over
the (Vi, Vj, S-configuration) contingency table, asymptotically χ² with
dof = (r_i−1)(r_j−1)·Π_{k∈S} r_k. The batched engines (core/levels.py
``chunk_g2`` → kernels/gsq.py) histogram a joint code per worklist cell;
the serial per-triple oracle lives in core/stable_ref.g2_test.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import ClassVar, NamedTuple, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import ndtri

from repro import obs

#: f32 matmuls here run at full f32 precision: the TPU's default rounds
#: operands to bf16, which moves C and partial correlations by ~1e-3 and
#: flips tests near τ. On the CPU this changes no bit.
_HI = jax.lax.Precision.HIGHEST

#: Hard cap on one G² worklist cell's contingency-table width
#: K = r^(ℓ+2): the table is unrolled in the kernel/reference reduction,
#: so K bounds both trace size and VMEM accumulator rows.
MAX_G2_TABLE = 4096


def fisher_z(rho: jax.Array) -> jax.Array:
    """|½ ln((1+ρ)/(1−ρ))| = |atanh ρ|, with clipping for |ρ|→1 (Eq. 6).

    Written as ½(log1p ρ − log1p(−ρ)) rather than ``jnp.arctanh``: Mosaic
    has no atanh lowering, and this is the expansion XLA applies to atanh,
    so the jnp engines, the kernel references and the Pallas kernels (which
    all call this function) share one op sequence and stay bit-identical to
    the former ``arctanh`` decisions on the CPU."""
    rho = jnp.clip(rho, -0.9999999, 0.9999999)
    return jnp.abs(0.5 * (jnp.log1p(rho) - jnp.log1p(-rho)))


def threshold(m: int, ell: int, alpha: float, *,
              insufficient: str = "raise") -> float:
    """τ = Φ⁻¹(1−α/2)/√(m−ℓ−3)  (Eq. 7), returned as a host float. Φ⁻¹ is
    ``jax.scipy.special.ndtri``, which runs on the default device; its
    scalar is read back through ``obs.fetch`` (a counted host sync).

    When m − ℓ − 3 ≤ 0 the statistic's variance normaliser is undefined —
    the level cannot be tested at this sample count. ``insufficient``
    selects the failure mode:

      "raise"  (default) raise :class:`~repro.core.validate.InsufficientSamplesError`;
      "warn"   warn once and clamp the denominator to 1 (``pc()``'s level
               loop uses this: validated entry points only reach it at
               levels beyond the validated depth, where a loud skip-grade
               τ beats aborting a mostly-finished run);
      "clamp"  the pre-fix silent behaviour, kept as an explicit opt-in.
    """
    denom = m - ell - 3
    if denom <= 0:
        if insufficient not in ("raise", "warn", "clamp"):
            raise ValueError(
                f"insufficient must be raise|warn|clamp, got {insufficient!r}"
            )
        msg = (
            f"m={m} samples cannot support a level-{ell} Fisher-z test: the "
            f"threshold needs m - ell - 3 > 0 (got {denom}). The clamped "
            "τ rejects (keeps) every edge at this level. Collect more "
            f"samples or cap max_level at {max(m - 4, 0)}."
        )
        if insufficient == "raise":
            from .validate import InsufficientSamplesError

            raise InsufficientSamplesError(msg)
        if insufficient == "warn":
            warnings.warn(msg, stacklevel=2)
        denom = 1
    z = obs.fetch(ndtri(1.0 - alpha / 2.0), site="cit.threshold")
    return float(z) / float(denom) ** 0.5


def pseudo_inverse(m2: jax.Array) -> jax.Array:
    """Paper Alg. 7 (Courrieu): Moore–Penrose inverse via full-rank Cholesky.

        L = cholesky(M2ᵀ M2) ;  R = (Lᵀ L)⁻¹ ;  M2⁺ = L R R Lᵀ M2ᵀ

    Works batched over leading dims. For rank-deficient M2 the full-rank
    Cholesky would need column pruning; following pcalg practice we add a
    tiny ridge — real gene-expression matrices are full rank up to noise.
    """
    mt_m = jnp.einsum("...ji,...jk->...ik", m2, m2, precision=_HI)
    eye = jnp.eye(m2.shape[-1], dtype=m2.dtype)
    ridge = 1e-10 * jnp.trace(mt_m, axis1=-2, axis2=-1)[..., None, None] + 1e-30
    l = jnp.linalg.cholesky(mt_m + ridge * eye)
    lt_l = jnp.einsum("...ji,...jk->...ik", l, l, precision=_HI)
    r = jnp.linalg.inv(lt_l)
    return jnp.einsum(
        "...ij,...jk,...kl,...ml,...nm->...in", l, r, r, l, m2, precision=_HI
    )


def solve_spd(m2: jax.Array, rhs: jax.Array, jitter: float = 1e-8) -> jax.Array:
    """Fast path: Cholesky solve of the SPD correlation submatrix."""
    eye = jnp.eye(m2.shape[-1], dtype=m2.dtype)
    chol = jnp.linalg.cholesky(m2 + jitter * eye)
    return jax.scipy.linalg.cho_solve((chol, True), rhs)


def partial_corr_single(
    c: jax.Array, i: jax.Array, j: jax.Array, s: jax.Array, robust: bool = False
) -> jax.Array:
    """ρ(Vi, Vj | S) for one (i, j, S) triple. s: int vector of size ℓ.

    Reference-grade (used by the serial oracle and tests); the batched
    engines in levels.py inline the same math over worklists.
    """
    ell = s.shape[-1]
    if ell == 0:
        return c[i, j]
    m2 = c[jnp.ix_(s, s)] if s.ndim == 1 else None
    ci_s = c[i, s]
    cj_s = c[j, s]
    if robust:
        g = pseudo_inverse(m2)
        gi = jnp.matmul(g, ci_s, precision=_HI)
        gj = jnp.matmul(g, cj_s, precision=_HI)
    else:
        gi = solve_spd(m2, ci_s)
        gj = solve_spd(m2, cj_s)
    h01 = c[i, j] - jnp.matmul(ci_s, gj, precision=_HI)
    h00 = c[i, i] - jnp.matmul(ci_s, gi, precision=_HI)
    h11 = c[j, j] - jnp.matmul(cj_s, gj, precision=_HI)
    denom = jnp.sqrt(jnp.maximum(h00 * h11, 1e-30))
    return h01 / denom


def correlation_from_samples(x: jax.Array) -> jax.Array:
    """Sample correlation matrix, x: (m, n) → (n, n), fp32.

    The production path uses the tiled Pallas kernel in kernels/corr.py;
    this is the mathematical definition both are tested against.
    """
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=0, keepdims=True)
    xc = x - mu
    std = jnp.sqrt(jnp.mean(xc * xc, axis=0, keepdims=True))
    xn = xc / jnp.maximum(std, 1e-30)
    c = jnp.matmul(xn.T, xn, precision=_HI) / x.shape[0]
    # exact-1 diagonal guards atanh in level 0
    return jnp.clip(c, -1.0, 1.0).at[jnp.arange(x.shape[1]), jnp.arange(x.shape[1])].set(1.0)


# ---------------------------------------------------------------------------
# the CITest seam: statistic + per-level decision scalar + sufficient stats
# ---------------------------------------------------------------------------
class DiscreteStats(NamedTuple):
    """Sufficient statistics of the discrete G² test — a jax pytree the
    engines thread through the same slot the Gaussian path uses for C.

    codes:   (m, n) int32 level codes in [0, arity_k) per column k;
    arities: (n,)   int32 per-variable arity (observed-or-declared level
             count — feeds the dof formula, NOT the code stride: the
             engines stride by the run-wide max arity so every variable
             shares one static table layout).
    """

    codes: jax.Array
    arities: jax.Array


@runtime_checkable
class CITest(Protocol):
    """What the drivers (core/pc.py, core/engines.py, batch/scan_pc.py)
    need from a conditional-independence test:

      kind                   stable routing tag ("gaussian" | "discrete");
      m / alpha              sample count and significance level;
      tau(ell)               the per-level decision SCALAR fed to the
                             engines as trace data — the Fisher-z τ for
                             Gaussian, α itself for p-value-space tests;
      taus(max_level)        the whole tau vector (the traced-scan path's
                             data input);
      stats_from_samples(x)  raw samples → the pytree the engines consume
                             (C for Gaussian, DiscreteStats for G²);
      level0(stats, tau)     the fused unconditional pass → (n, n) bool.

    Instances must be hashable (frozen dataclasses): they ride in jit
    static arguments and lru_cache keys.
    """

    kind: str
    m: int
    alpha: float

    def tau(self, ell: int, *, insufficient: str = "raise") -> float: ...

    def taus(self, max_level: int, *,
             insufficient: str = "raise") -> tuple: ...

    def stats_from_samples(self, x): ...

    def level0(self, stats, tau): ...


@dataclasses.dataclass(frozen=True)
class GaussianCITest:
    """The paper's Fisher-z partial-correlation test as a CITest object.

    Bit-identity contract: every method delegates to the exact module-level
    machinery the pre-refactor drivers called (``threshold``,
    ``correlation_from_samples``, ``levels.level0``), so routing through
    the test object cannot perturb a single decision — asserted by
    tests/test_cit.py and the (engine × test) matrix in tests/test_engines.py.
    """

    m: int
    alpha: float = 0.01
    kind: ClassVar[str] = "gaussian"

    def tau(self, ell: int, *, insufficient: str = "raise") -> float:
        return threshold(self.m, ell, self.alpha, insufficient=insufficient)

    def taus(self, max_level: int, *, insufficient: str = "raise") -> tuple:
        return tuple(self.tau(ell, insufficient=insufficient)
                     for ell in range(max_level + 1))

    def stats_from_samples(self, x) -> jax.Array:
        return correlation_from_samples(jnp.asarray(x))

    def level0(self, stats, tau):
        from . import levels as L

        return L.level0(stats, tau)


def encode_discrete(x) -> tuple:
    """Host-side encoding of a categorical sample matrix: (m, n) integer
    levels → (DiscreteStats, r_max). Codes are kept verbatim (validation
    guarantees 0-based integers); arities are per-column ``max + 1`` so
    declared-but-unobserved top levels still count toward dof the way the
    serial oracle counts them.
    """
    codes = np.asarray(x).astype(np.int32)
    arities = codes.max(axis=0).astype(np.int32) + 1
    r_max = int(arities.max(initial=1))
    return (
        DiscreteStats(codes=jnp.asarray(codes), arities=jnp.asarray(arities)),
        r_max,
    )


@dataclasses.dataclass(frozen=True)
class DiscreteCITest:
    """Contingency-table G²/χ² test over integer level codes.

    The per-level decision scalar is α itself: each worklist cell computes
    its own dof-aware p-value ``chi2.sf(G², dof) = gammaincc(dof/2, G²/2)``
    and declares independence when p ≥ α — the same boundary semantics as
    the Gaussian ``Z ≤ τ`` rule (the boundary counts as independent).

    ``r`` is the run-wide maximum arity — a STATIC shape parameter: the
    engines stride every variable's code by r so one compiled table layout
    (K = r^(ℓ+2) cells) serves the whole worklist; slots above a
    variable's true arity stay empty and contribute nothing to G², while
    dof uses the true per-variable arities from :class:`DiscreteStats`.
    """

    m: int
    alpha: float = 0.01
    r: int = 2
    kind: ClassVar[str] = "discrete"

    @classmethod
    def from_samples(cls, x, alpha: float = 0.01):
        """(test, stats) from raw categorical samples (validated upstream)."""
        stats, r_max = encode_discrete(x)
        return cls(m=int(stats.codes.shape[0]), alpha=float(alpha), r=r_max), stats

    def tau(self, ell: int, *, insufficient: str = "raise") -> float:
        del ell, insufficient  # dof-awareness lives per-cell, not per-level
        return float(self.alpha)

    def taus(self, max_level: int, *, insufficient: str = "raise") -> tuple:
        return tuple(self.tau(ell, insufficient=insufficient)
                     for ell in range(max_level + 1))

    def stats_from_samples(self, x) -> DiscreteStats:
        return encode_discrete(x)[0]

    def level0(self, stats, tau):
        from . import levels as L

        return L.level0_g2(stats, tau, r=self.r)

    def table_width(self, ell: int) -> int:
        """K = r^(ℓ+2) cells per worklist entry at level ℓ."""
        return self.r ** (ell + 2)

    def max_supported_level(self) -> int:
        """Deepest ℓ whose table fits MAX_G2_TABLE — the default level cap
        ``pc()`` applies when the caller leaves max_level unset (an explicit
        deeper max_level still raises via :meth:`check_level`)."""
        ell = 0
        while self.table_width(ell + 1) <= MAX_G2_TABLE:
            ell += 1
        return ell

    def check_level(self, ell: int):
        """Static trace-size guard: the G² reduction unrolls over K."""
        k = self.table_width(ell)
        if k > MAX_G2_TABLE:
            raise ValueError(
                f"level {ell} needs a {k}-cell contingency table per test "
                f"(max arity {self.r}) — beyond MAX_G2_TABLE={MAX_G2_TABLE}. "
                "Cap max_level, re-bin high-arity columns, or raise the cap "
                "if the trace/VMEM budget allows."
            )


def resolve_citest(test, m: int, alpha: float):
    """Normalise the public ``test`` argument: None/"gaussian"/"discrete"
    or a CITest instance → a concrete instance. String forms bind (m, α)
    from the call; instances are trusted as-is (their α wins so a test
    object built once keeps meaning the same hypothesis test)."""
    if test is None or test == "gaussian":
        return GaussianCITest(m=int(m), alpha=float(alpha))
    if test == "discrete":
        return DiscreteCITest(m=int(m), alpha=float(alpha))
    if isinstance(test, (GaussianCITest, DiscreteCITest)):
        return test
    if isinstance(test, CITest):
        return test
    raise ValueError(
        f"test must be None, 'gaussian', 'discrete', or a CITest instance; "
        f"got {test!r}"
    )
