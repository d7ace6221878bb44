"""Correctness of the PC core: engines vs serial oracle, combinadics,
compaction, CI math, orientation. Includes hypothesis property tests."""
import itertools

import numpy as np
import pytest
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core import pc, pc_from_corr
from repro.core.cit import (
    correlation_from_samples,
    fisher_z,
    partial_corr_single,
    pseudo_inverse,
    threshold,
)
from repro.core.combinadics import (
    binom_table,
    n_choose_l,
    rank_of_combination,
    unrank_combination,
    unrank_excluding,
)
from repro.core.compact import compact_rows, compact_rows_np
from repro.core.orient import cpdag_from_skeleton, cpdag_np
from repro.configs.cupc_datasets import CUPC_DATASETS
from repro.core.stable_ref import pc_stable_skeleton
from repro.data.synthetic_dag import (
    d_separated,
    oracle_pc_stable,
    sample_gaussian_dag,
)


# ---------------------------------------------------------------- combinadics
@pytest.mark.parametrize("n,ell", [(5, 2), (8, 3), (10, 1), (12, 4), (6, 5)])
def test_unrank_matches_itertools(n, ell):
    expect = list(itertools.combinations(range(n), ell))
    got = unrank_combination(jnp.arange(len(expect)), n, ell)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(expect))


@given(st.integers(2, 16), st.integers(1, 5), st.data())
@settings(max_examples=50, deadline=None)
def test_unrank_rank_roundtrip(n, ell, data):
    ell = min(ell, n)
    total = n_choose_l(n, ell)
    t = data.draw(st.integers(0, total - 1))
    combo = np.asarray(unrank_combination(jnp.asarray([t]), n, ell))[0]
    assert len(set(combo.tolist())) == ell  # distinct
    assert (np.diff(combo) > 0).all()  # sorted
    assert rank_of_combination(combo, n) == t


@pytest.mark.parametrize("n,ell,p", [(6, 2, 0), (6, 2, 3), (6, 2, 5), (9, 3, 4)])
def test_unrank_excluding(n, ell, p):
    expect = [c for c in itertools.combinations(range(n), ell) if p not in c]
    got = unrank_excluding(jnp.arange(len(expect)), n, ell, p)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(expect))


def test_binom_table():
    t = binom_table(20)
    import math

    for n in range(21):
        for k in range(min(n, 17) + 1):
            assert t[n, k] == math.comb(n, k)


# ------------------------------------------------------------------- compact
@given(st.integers(2, 40), st.floats(0.0, 1.0), st.integers(0, 2**31 - 1))
@settings(max_examples=40, deadline=None)
def test_compact_matches_numpy(n, dens, seed):
    rng = np.random.default_rng(seed)
    a = rng.random((n, n)) < dens
    a = np.triu(a, 1)
    a = a | a.T
    cj, countsj = compact_rows(jnp.asarray(a))
    cn, countsn = compact_rows_np(a)
    np.testing.assert_array_equal(np.asarray(countsj), countsn)
    np.testing.assert_array_equal(np.asarray(cj), cn)


# ----------------------------------------------------------------------- cit
def test_fisher_z_threshold_values():
    # pcalg reference: qnorm(1 - 0.01/2)/sqrt(100 - 0 - 3) = 2.5758/9.849
    assert abs(threshold(100, 0, 0.01) - 2.5758293 / np.sqrt(97)) < 1e-6
    assert abs(float(fisher_z(jnp.float32(0.5))) - abs(np.arctanh(0.5))) < 1e-6


def test_partial_corr_matches_numpy_pinv():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(500, 8))
    c = np.corrcoef(x.T)
    from repro.core.stable_ref import _partial_corr

    for s in [(2,), (2, 3), (2, 3, 4), (5, 6, 7)]:
        ref = _partial_corr(c, 0, 1, s)
        got = float(
            partial_corr_single(jnp.asarray(c, jnp.float32), 0, 1, jnp.asarray(s))
        )
        assert abs(ref - got) < 1e-4


def test_pseudo_inverse_matches_pinv():
    rng = np.random.default_rng(1)
    for k in (1, 2, 3, 5):
        a = rng.normal(size=(k, k))
        m = a @ a.T + 0.1 * np.eye(k)  # SPD
        got = np.asarray(pseudo_inverse(jnp.asarray(m, jnp.float32)))
        np.testing.assert_allclose(got, np.linalg.pinv(m), rtol=2e-3, atol=2e-4)


def test_correlation_from_samples():
    rng = np.random.default_rng(2)
    x = rng.normal(size=(1000, 6))
    got = np.asarray(correlation_from_samples(jnp.asarray(x)))
    np.testing.assert_allclose(got, np.corrcoef(x.T), atol=2e-3)


def test_inv_spd_jitter_scales_with_diagonal():
    """Satellite: the Tikhonov jitter in levels._inv_spd is RELATIVE to the
    block's diagonal magnitude, not an absolute 1e-8 — so inverting a
    rescaled SPD block is scale-invariant (inv(s·M)·s == inv(M) up to fp),
    which a fixed jitter breaks for blocks whose scale dwarfs it. For unit
    diagonals (every correlation block) the scale factor is exactly 1, so
    correlation results are untouched bit-for-bit."""
    from repro.core.levels import _inv_spd

    b = 1.0 - 1e-3
    m2 = np.array([[1.0, b], [b, 1.0]], np.float32)  # ill-conditioned block
    base = np.asarray(_inv_spd(jnp.asarray(m2)[None]))[0]
    for scale in (1e-6, 1e-4, 1e4):
        scaled = np.asarray(_inv_spd(jnp.asarray(m2 * scale)[None]))[0] * scale
        np.testing.assert_allclose(scaled, base, rtol=2e-3)


def test_ill_conditioned_fixture_matches_stable_ref():
    """Satellite regression: near-duplicate variables make M2 blocks
    near-singular — the regime where a biased inverse can flip CI decisions
    away from the pseudo-inverse oracle. The jnp engine must still agree
    with stable_ref's skeleton on this fixture."""
    rng = np.random.default_rng(0)
    m, n = 2000, 12
    x, _ = sample_gaussian_dag(n=n, m=m, density=0.3, seed=3)
    x = np.asarray(x).copy()
    x[:, 5] = x[:, 4] + 1e-4 * rng.standard_normal(m)  # corr(4,5) ≈ 1 - 2e-7
    c = correlation_from_samples(jnp.asarray(x))
    assert float(np.asarray(c)[4, 5]) > 1.0 - 1e-6, "fixture not ill-conditioned"
    ref = pc_stable_skeleton(np.asarray(c), m=m, alpha=0.01)
    run = pc_from_corr(c, m, alpha=0.01, engine="S")
    np.testing.assert_array_equal(run.adj, ref.adj)


# --------------------------------------------------- engines vs serial oracle
@pytest.mark.parametrize("engine", ["S", "E"])
@pytest.mark.parametrize("n,density,seed", [(15, 0.2, 0), (20, 0.15, 1), (25, 0.1, 2), (12, 0.4, 3)])
def test_skeleton_matches_serial_reference(engine, n, density, seed):
    x, _ = sample_gaussian_dag(n=n, m=3000, density=density, seed=seed)
    c = np.asarray(correlation_from_samples(jnp.asarray(x)))
    ref = pc_stable_skeleton(c, m=3000, alpha=0.01)
    run = pc(x, alpha=0.01, engine=engine)
    np.testing.assert_array_equal(run.adj, ref.adj)


@pytest.mark.parametrize("engine", ["S", "E"])
def test_engines_agree_with_each_other_and_small_chunks(engine):
    """Chunked early-termination must not change the skeleton (order
    independence, paper §2.4)."""
    x, _ = sample_gaussian_dag(n=18, m=2000, density=0.25, seed=7)
    big = pc(x, engine=engine, cell_budget=2**24)
    small = pc(x, engine=engine, cell_budget=2**10)  # many chunks per level
    np.testing.assert_array_equal(big.adj, small.adj)


def test_sepsets_are_valid_separators():
    """Every recorded sepset must actually pass the CI test it claims."""
    x, _ = sample_gaussian_dag(n=18, m=3000, density=0.25, seed=11)
    c = correlation_from_samples(jnp.asarray(x))
    run = pc(x, alpha=0.01, engine="S")
    n = run.adj.shape[0]
    checked = 0
    for i in range(n):
        for j in range(i + 1, n):
            s = run.sepsets[i, j]
            if run.adj[i, j] or s[0] == -2:  # edge alive or level-0 removal
                continue
            ids = s[s >= 0]
            if len(ids) == 0:
                continue
            rho = partial_corr_single(c, i, j, jnp.asarray(ids))
            tau = threshold(3000, len(ids), 0.01)
            assert float(fisher_z(rho)) <= tau, (i, j, ids)
            checked += 1
    assert checked > 0


def test_order_independence_variable_permutation():
    """PC-stable is order independent: permuting variables must permute the
    skeleton (paper's key property)."""
    x, _ = sample_gaussian_dag(n=15, m=2500, density=0.25, seed=5)
    run = pc(x, engine="S")
    perm = np.random.default_rng(0).permutation(15)
    run_p = pc(x[:, perm], engine="S")
    np.testing.assert_array_equal(run_p.adj, run.adj[np.ix_(perm, perm)])


# ----------------------------------------------------------- orientation/CPDAG
def test_dsep_oracle_sanity():
    # chain 0 -> 1 -> 2: 0 ⟂ 2 | 1, not 0 ⟂ 2
    from repro.data.synthetic_dag import GaussianDAG

    adj = np.zeros((3, 3), bool)
    adj[1, 0] = True  # 0 -> 1
    adj[2, 1] = True  # 1 -> 2
    dag = GaussianDAG(weights=adj.astype(float), adj=adj)
    assert not d_separated(dag, 0, 2, ())
    assert d_separated(dag, 0, 2, (1,))
    # collider 0 -> 1 <- 2
    adj = np.zeros((3, 3), bool)
    adj[1, 0] = True
    adj[1, 2] = True
    dag = GaussianDAG(weights=adj.astype(float), adj=adj)
    assert d_separated(dag, 0, 2, ())
    assert not d_separated(dag, 0, 2, (1,))


def test_vstructure_orientation_collider():
    """PC on collider data must orient 0→2←1."""
    rng = np.random.default_rng(0)
    m = 20000
    v0 = rng.normal(size=m)
    v1 = rng.normal(size=m)
    v2 = 0.8 * v0 + 0.8 * v1 + 0.3 * rng.normal(size=m)
    x = np.stack([v0, v1, v2], 1)
    run = pc(x, alpha=0.01)
    # skeleton: edges 0-2, 1-2 only
    expect = np.zeros((3, 3), bool)
    expect[0, 2] = expect[2, 0] = expect[1, 2] = expect[2, 1] = True
    np.testing.assert_array_equal(run.adj, expect)
    d = run.cpdag
    assert d[0, 2] and not d[2, 0]  # 0 → 2
    assert d[1, 2] and not d[2, 1]  # 1 → 2


@pytest.mark.parametrize("seed", [1, 3, 5, 9, 10])
def test_cpdag_recovers_true_equivalence_class(seed):
    """With ample data the engine CPDAG equals the oracle CPDAG built from
    exact d-separation (true Markov equivalence class). Seeds are fixed to
    instances where finite-sample CI recovers the population graph — on other
    seeds PC (any implementation, incl. pcalg) picks statistically different
    sepsets; that sensitivity is inherent to the algorithm, not the engine."""
    x, dag = sample_gaussian_dag(n=10, m=100_000, density=0.25, seed=seed)
    adj_o, sep_o = oracle_pc_stable(dag)
    cp_o = cpdag_np(adj_o, sep_o)
    run = pc(x, alpha=0.01, engine="S")
    np.testing.assert_array_equal(run.adj, adj_o)
    np.testing.assert_array_equal(run.cpdag, cp_o)


def test_meek_jax_matches_np_reference():
    rng = np.random.default_rng(3)
    for seed in range(5):
        x, dag = sample_gaussian_dag(n=9, m=60_000, density=0.3, seed=seed + 50)
        adj_o, sep_o = oracle_pc_stable(dag)
        cp_np = cpdag_np(adj_o, sep_o)
        # build the engine sep tensor from the oracle dict
        n = adj_o.shape[0]
        sep = -np.ones((n, n, 8), np.int32)
        for (i, j), s in sep_o.items():
            sep[i, j, : len(s)] = s
            sep[j, i, : len(s)] = s
        cp_j = np.asarray(cpdag_from_skeleton(jnp.asarray(adj_o), jnp.asarray(sep)))
        np.testing.assert_array_equal(cp_j, cp_np, err_msg=f"seed={seed}")


# -------------------------------------------------------------- property: PC
@given(st.integers(0, 10_000))
@settings(max_examples=10, deadline=None)
def test_property_skeleton_subset_of_moral_structure(seed):
    """Engine skeleton ⊆ level-0 skeleton (levels only remove edges)."""
    x, _ = sample_gaussian_dag(n=12, m=1500, density=0.3, seed=seed)
    c = correlation_from_samples(jnp.asarray(x))
    from repro.core.levels import level0

    adj0 = np.asarray(level0(c, threshold(1500, 0, 0.01)))
    run = pc_from_corr(c, 1500, engine="S")
    assert not (run.adj & ~adj0).any()
    # symmetry + no self loops
    np.testing.assert_array_equal(run.adj, run.adj.T)
    assert not run.adj.diagonal().any()


# ------------------------------------------------------ entry-point validation
def test_constant_column_rejected_not_silent():
    """Regression (ISSUE-6): a constant column used to flow through
    correlation_from_samples as a row of fabricated zero correlations —
    universal "independence" with silent-NaN risk downstream. It must now
    die at the door as a typed, actionable error naming the column."""
    from repro.core.validate import ConstantColumnError

    x, _ = sample_gaussian_dag(n=10, m=500, density=0.2, seed=0)
    x = np.asarray(x).copy()
    x[:, 4] = 3.25
    with pytest.raises(ConstantColumnError, match=r"\[4\]"):
        pc(x, alpha=0.01, engine="S")


def test_f32_overflowing_column_rejected():
    """A column whose centred sum of squares passes the float32 range (raw
    |x| ≈ 2e19 over 850 samples does) would standardise to zeros on the
    device: reject it at the door."""
    from repro.core.validate import SampleScaleError

    x, _ = sample_gaussian_dag(n=10, m=500, density=0.2, seed=0)
    x = np.asarray(x).copy()
    x[:, 7] *= 1e19
    with pytest.raises(SampleScaleError, match=r"first: 7"):
        pc(x, alpha=0.01, engine="S")
    x[:, 7] /= 1e19
    pc(x, alpha=0.01, engine="S")  # in range: runs


@pytest.mark.parametrize("name", sorted(CUPC_DATASETS))
def test_published_dataset_samples_pass_admission(name):
    """What ``pc_run --dataset <name>`` (and the benchmarks and pc_serve)
    feed pc() passes admission at the published size: the generator's
    columns are standardised, so no f32 sum of squares overflows."""
    from repro.core.validate import validate_samples

    ds = CUPC_DATASETS[name]
    x, _ = sample_gaussian_dag(n=ds.n, m=ds.m, density=ds.density, seed=0)
    assert validate_samples(x) == (ds.m, ds.n)
    np.testing.assert_allclose(x.std(axis=0), 1.0, rtol=1e-12)


def test_nonfinite_inputs_rejected_with_typed_errors():
    from repro.core.validate import NonFiniteDataError

    x, _ = sample_gaussian_dag(n=10, m=500, density=0.2, seed=1)
    x = np.asarray(x).copy()
    x[7, 2] = np.nan
    with pytest.raises(NonFiniteDataError):
        pc(x)
    c = np.asarray(correlation_from_samples(
        jnp.asarray(sample_gaussian_dag(n=10, m=500, density=0.2, seed=1)[0])))
    c_bad = c.copy()
    c_bad[1, 2] = c_bad[2, 1] = np.inf
    with pytest.raises(NonFiniteDataError):
        pc_from_corr(c_bad, 500)


def test_bad_correlation_matrix_rejected():
    from repro.core.validate import BadCorrelationError

    c = np.asarray(correlation_from_samples(
        jnp.asarray(sample_gaussian_dag(n=8, m=400, density=0.2, seed=2)[0])))
    asym = c.copy()
    asym[0, 1] += 0.05
    with pytest.raises(BadCorrelationError):
        pc_from_corr(asym, 400)
    cov = c * 4.0  # a covariance is not a correlation
    with pytest.raises(BadCorrelationError):
        pc_from_corr(cov, 400)


def test_m_guards_warn_or_reject():
    """m < n (the paper's gene-expression regime) warns but RUNS; too few
    samples for the requested depth is a hard typed error; strict mode
    (the serving admission policy) escalates m < n to an error."""
    from repro.core.validate import RankDeficientError, validate_corr

    x, _ = sample_gaussian_dag(n=12, m=500, density=0.2, seed=3)
    c = np.asarray(correlation_from_samples(jnp.asarray(x)))
    with pytest.warns(UserWarning, match="rank-deficient"):
        run = pc_from_corr(c, 10, max_level=1)
    assert run.adj.shape == (12, 12)
    with pytest.raises(RankDeficientError):
        pc_from_corr(c, 10, max_level=7)  # m - level - 3 = 0: no valid test
    with pytest.raises(RankDeficientError):
        validate_corr(c, 10, max_level=1, strict_rank=True)


def test_validate_false_restores_trusting_entry():
    """validate=False is the explicit opt-out for callers that already
    validated upstream (pc() itself uses it when delegating)."""
    x, _ = sample_gaussian_dag(n=10, m=500, density=0.2, seed=4)
    x = np.asarray(x).copy()
    x[:, 0] = 1.0  # constant column: allowed through when opted out
    run = pc(x, engine="S", validate=False)
    assert run.adj.shape == (10, 10)


# ------------------------------------- threshold: the silent clamp is gone
def test_threshold_insufficient_raises_regression():
    """m − ℓ − 3 ≤ 0 used to floor the denominator to 1 SILENTLY, turning
    every test at that level into a guaranteed edge-keep; the library
    default now raises a typed error, pc()'s level loop opts into a loud
    warn-and-clamp, and the old behaviour survives only as an explicit
    opt-in."""
    from repro.core.validate import InsufficientSamplesError, ValidationError

    with pytest.raises(InsufficientSamplesError):
        threshold(6, 3, 0.01)  # denom = 0
    with pytest.raises(InsufficientSamplesError):
        threshold(2, 0, 0.01)  # denom < 0
    assert issubclass(InsufficientSamplesError, ValidationError)

    with pytest.warns(UserWarning, match="cannot support"):
        t_warn = threshold(6, 3, 0.01, insufficient="warn")
    t_clamp = threshold(6, 3, 0.01, insufficient="clamp")
    assert t_warn == t_clamp  # same clamped value, different loudness

    # the healthy regime is untouched by the guard
    assert threshold(100, 0, 0.01) == pytest.approx(
        2.5758293 / np.sqrt(97), abs=1e-6
    )
    assert threshold(100, 0, 0.01) == threshold(100, 0, 0.01, insufficient="clamp")
