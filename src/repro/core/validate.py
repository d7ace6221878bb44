"""Dataset / correlation-matrix admission validation — the hostile-input
front door shared by the public entry points and the serving layer.

The engine stack assumes a clean Gaussian dataset: finite samples,
non-constant columns, enough samples for the Fisher-z thresholds to mean
anything. Violations don't crash the traced programs — they silently
poison them (a NaN anywhere in C makes every partial correlation of the
affected rows NaN, `fisher_z(NaN) <= tau` is False, and the edge is
silently KEPT; a constant column zeroes its correlations and fabricates
independence). This module turns each failure mode into a TYPED error
with an actionable message, raised BEFORE any device dispatch:

  * :class:`NonFiniteDataError`     — NaN/Inf in samples or C
  * :class:`ConstantColumnError`    — zero-variance column (corr undefined)
  * :class:`SampleScaleError`       — a column whose centred sum of squares
                                      overflows f32 (the correlation would
                                      read it as all-zero)
  * :class:`RankDeficientError`     — too few samples for the requested
                                      test depth (m ≤ max_level + 3), or
                                      m < n in strict mode (sample
                                      correlation necessarily singular)
  * :class:`BadCorrelationError`    — a "correlation" matrix that isn't
                                      (shape, symmetry, diagonal, range)

`pc()` / `pc_from_corr` (core/pc.py) call these with ``strict_rank=False``
— the paper's own gene-expression datasets have m < n by design, so that
regime only warns. The serving layer (repro/serve) validates with
``strict_rank=True`` at admission: a multi-tenant endpoint rejects or
quarantines rank-deficient panels instead of serving silently biased
graphs, and a rejected request never reaches a batch slot (its slot-mates
are unaffected — tests/test_serve.py).

All checks are host-side numpy on data the entry points are about to ship
to the device anyway; cost is one O(m·n + n²) pass.
"""
from __future__ import annotations

import sys
import warnings

import numpy as np

from repro import obs


class ValidationError(ValueError):
    """Base class of every admission failure. ``code`` is a stable
    machine-readable tag (the serving layer's rejection records carry it)."""

    code = "invalid"


class NonFiniteDataError(ValidationError):
    code = "non_finite"


class ConstantColumnError(ValidationError):
    code = "constant_column"


class SampleScaleError(ValidationError):
    code = "sample_scale"


class RankDeficientError(ValidationError):
    code = "rank_deficient"


class BadCorrelationError(ValidationError):
    code = "bad_correlation"


class InsufficientSamplesError(ValidationError):
    """Fisher-z threshold asked for at a level the sample count cannot
    support (m − ℓ − 3 ≤ 0). Previously ``cit.threshold`` silently floored
    the denominator to 1, producing a huge τ that keeps every edge at that
    level without any signal — now the caller chooses: raise (library
    default), warn + clamp (``pc()``'s level loop), or silent clamp
    (explicit legacy opt-in)."""

    code = "insufficient_samples"


class BadDiscreteDataError(ValidationError):
    code = "bad_discrete_data"


def _as_host(x) -> np.ndarray:
    """Materialise on host without importing jax at module import time; a
    device array is read back through ``obs.fetch`` (a counted host sync)."""
    jax = sys.modules.get("jax")
    if jax is not None and isinstance(x, jax.Array):
        return np.asarray(obs.fetch(x, site="validate"))
    return np.asarray(x)


def _check_m(m: int, n: int, max_level: int | None, strict_rank: bool):
    """Shared sample-count guards for both entry shapes."""
    lmax = 3 if max_level is None else int(max_level)
    if m <= lmax + 3:
        raise RankDeficientError(
            f"m={m} samples cannot support conditional-independence tests up "
            f"to level {lmax}: the Fisher-z threshold needs m - level - 3 > 0 "
            f"(got {m - lmax - 3}). Collect more samples or lower max_level "
            f"to at most {max(m - 4, 0)}."
        )
    if m < n:
        msg = (
            f"m={m} samples < n={n} variables: the sample correlation matrix "
            "is rank-deficient, so conditioning sets larger than the true "
            "rank are tested against a singular block (regularised, but "
            "biased). Prefer more samples, a lower max_level, or the "
            "bootstrap ensemble for stability."
        )
        if strict_rank:
            raise RankDeficientError(msg)
        warnings.warn(msg, stacklevel=3)


def validate_samples(x, max_level: int | None = None,
                     strict_rank: bool = False) -> tuple[int, int]:
    """Validate a raw sample matrix x: (m, n). Returns (m, n).

    Raises :class:`NonFiniteDataError` / :class:`ConstantColumnError` /
    :class:`RankDeficientError` with actionable messages; ``strict_rank``
    escalates the m < n warning to an error (serving admission policy).
    """
    x = _as_host(x)
    if x.ndim != 2:
        raise ValidationError(
            f"expected a (m, n) sample matrix; got shape {x.shape}"
        )
    m, n = int(x.shape[0]), int(x.shape[1])
    finite = np.isfinite(x)
    if not finite.all():
        bad = np.argwhere(~finite)
        r, c = int(bad[0][0]), int(bad[0][1])
        raise NonFiniteDataError(
            f"samples contain {len(bad)} non-finite value(s) (first at row "
            f"{r}, column {c}: {x[r, c]!r}). Impute or drop the affected "
            "rows/columns before calling pc() — NaN propagates into every "
            "partial correlation of that column and silently keeps edges."
        )
    span = x.max(axis=0) - x.min(axis=0)
    const = np.flatnonzero(span == 0)
    if const.size:
        cols = ", ".join(str(int(k)) for k in const[:8])
        more = "" if const.size <= 8 else f" (+{const.size - 8} more)"
        raise ConstantColumnError(
            f"column(s) [{cols}]{more} are constant: correlation with a "
            "zero-variance variable is undefined, and the previous behaviour "
            "silently reported it as 0 (fabricating independence). Drop the "
            "constant columns (np.delete(x, cols, axis=1)) or add measurement "
            "noise before calling pc()."
        )
    xc = x.astype(np.float64) - x.mean(axis=0, dtype=np.float64)
    ss = np.einsum("ij,ij->j", xc, xc)
    big = np.flatnonzero(ss > np.finfo(np.float32).max)
    if big.size:
        raise SampleScaleError(
            f"{big.size} column(s) (first: {int(big[0])}) have a centred sum "
            f"of squares of {ss[big[0]]:.3g}, past the float32 range the "
            "device standardises in: the correlation would treat them as "
            "zero-variance. Standardise the columns (x - mean) / std on the "
            "host before calling pc()."
        )
    _check_m(m, n, max_level, strict_rank)
    return m, n


def validate_corr(c, m: int, max_level: int | None = None,
                  strict_rank: bool = False,
                  sym_tol: float = 1e-4) -> int:
    """Validate a correlation matrix c: (n, n) plus its sample count m.
    Returns n.

    Checks shape/symmetry/unit-diagonal/[-1, 1]-range (within fp gemm
    tolerance — everything ``cit.correlation_from_samples`` and the MXU
    kernel produce passes bit-exactly), finiteness, and the same sample-
    count guards as :func:`validate_samples`. Ill-CONDITIONED (but valid)
    matrices pass — conditioning is a degradation-ladder concern
    (repro/serve), not an admission one.
    """
    c = _as_host(c)
    if c.ndim != 2 or c.shape[0] != c.shape[1]:
        raise BadCorrelationError(
            f"expected a square (n, n) correlation matrix; got shape {c.shape}"
        )
    n = int(c.shape[0])
    finite = np.isfinite(c)
    if not finite.all():
        bad = np.argwhere(~finite)
        i, j = int(bad[0][0]), int(bad[0][1])
        raise NonFiniteDataError(
            f"correlation matrix contains {len(bad)} non-finite value(s) "
            f"(first at C[{i}, {j}] = {c[i, j]!r}) — typically a constant "
            "column fed through np.corrcoef. Rebuild C with "
            "repro.core.cit.correlation_from_samples (which validates via "
            "pc()) or clean the offending columns."
        )
    if not np.allclose(c, c.T, atol=sym_tol, rtol=0.0):
        ij = np.unravel_index(np.abs(c - c.T).argmax(), c.shape)
        raise BadCorrelationError(
            f"correlation matrix is not symmetric (max |C - Cᵀ| at "
            f"{tuple(int(v) for v in ij)}: {abs(c - c.T).max():.3g}). "
            "Symmetrise with (C + C.T) / 2 if this is fp noise from an "
            "external pipeline."
        )
    diag = np.diagonal(c)
    if np.abs(diag - 1.0).max() > 1e-3:
        k = int(np.abs(diag - 1.0).argmax())
        raise BadCorrelationError(
            f"correlation diagonal must be 1 (C[{k}, {k}] = {diag[k]:.6g}). "
            "A covariance matrix? Normalise: C = cov / sqrt(outer(d, d)) "
            "with d = diag(cov)."
        )
    if np.abs(c).max() > 1.0 + 1e-5:
        ij = np.unravel_index(np.abs(c).argmax(), c.shape)
        raise BadCorrelationError(
            f"correlation entries must lie in [-1, 1]; C{tuple(int(v) for v in ij)} "
            f"= {c[ij]:.6g}. Clip or rebuild C."
        )
    _check_m(int(m), n, max_level, strict_rank)
    return n


def validate_discrete(x, max_level: int | None = None,
                      max_arity: int = 16) -> tuple[int, int]:
    """Validate a categorical sample matrix x: (m, n) of integer level codes.
    Returns (m, n).

    The discrete G² engine (core/cit.DiscreteCITest → kernels/gsq.py) builds
    contingency tables indexed by the raw codes, so admission is stricter
    than the Gaussian front door: codes must be finite non-negative
    integers, every column needs at least two OBSERVED levels (a constant
    column has zero degrees of freedom — G² ≡ 0 and the test fabricates
    independence for every edge it touches), and the maximum arity is
    capped (a single high-cardinality column multiplies every conditional
    table's size by its arity; re-bin such columns first). Sample-count
    adequacy is heuristic for contingency tables — the classical rule of
    thumb (≥ ~10 samples per unconditional cell) only WARNS, since sparse
    tables bias G² toward independence rather than poisoning the run.
    """
    x = _as_host(x)
    if x.ndim != 2:
        raise ValidationError(
            f"expected a (m, n) categorical sample matrix; got shape {x.shape}"
        )
    m, n = int(x.shape[0]), int(x.shape[1])
    finite = np.isfinite(x)
    if not finite.all():
        bad = np.argwhere(~finite)
        r, c = int(bad[0][0]), int(bad[0][1])
        raise NonFiniteDataError(
            f"categorical samples contain {len(bad)} non-finite value(s) "
            f"(first at row {r}, column {c}: {x[r, c]!r}). Impute or drop "
            "before calling pc(test='discrete')."
        )
    if not np.issubdtype(x.dtype, np.integer) and not np.array_equal(
            x, np.floor(x)):
        bad = np.argwhere(x != np.floor(x))
        r, c = int(bad[0][0]), int(bad[0][1])
        raise BadDiscreteDataError(
            f"categorical samples must be integer level codes; found "
            f"non-integer value {x[r, c]!r} at row {r}, column {c}. "
            "Discretise continuous variables (e.g. quantile binning) or use "
            "the Gaussian test."
        )
    if x.min(initial=0) < 0:
        bad = np.argwhere(x < 0)
        r, c = int(bad[0][0]), int(bad[0][1])
        raise BadDiscreteDataError(
            f"categorical level codes must be non-negative; found "
            f"{x[r, c]!r} at row {r}, column {c}. Re-encode levels as "
            "0..arity-1 (e.g. np.unique(col, return_inverse=True))."
        )
    n_levels = np.array([np.unique(x[:, k]).size for k in range(n)])
    const = np.flatnonzero(n_levels < 2)
    if const.size:
        cols = ", ".join(str(int(k)) for k in const[:8])
        more = "" if const.size <= 8 else f" (+{const.size - 8} more)"
        raise ConstantColumnError(
            f"column(s) [{cols}]{more} take a single observed level: a "
            "one-level variable has zero degrees of freedom, so every G² "
            "test involving it is vacuous (fabricated independence). Drop "
            "the constant columns before calling pc(test='discrete')."
        )
    arity = int(x.max()) + 1
    if arity > max_arity:
        k = int(np.argmax(x.max(axis=0)))
        raise BadDiscreteDataError(
            f"maximum arity {arity} (column {k}) exceeds the cap "
            f"{max_arity}: every conditioning variable multiplies the "
            "contingency-table width by its arity, so high-cardinality "
            "columns blow up the G² worklist. Re-bin the column or raise "
            "max_arity explicitly if the table budget allows."
        )
    if m < 10 * arity * arity:
        warnings.warn(
            f"m={m} samples for arity-{arity} variables gives fewer than "
            f"~10 samples per unconditional contingency cell "
            f"({arity * arity} cells); sparse tables bias G² toward "
            "independence. Prefer more samples or coarser bins.",
            stacklevel=3,
        )
    return m, n
