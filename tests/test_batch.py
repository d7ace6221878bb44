"""Batched PC subsystem (repro/batch/): bit-identical B=1 parity of the
traced scan vs the "S" engine, batched-vs-loop parity, the "scan" engine
registry wiring, bootstrap-ensemble invariants + reproducibility, the
orientation property test vs the serial oracle, and the vectorised
sepset_dict contract."""
import numpy as np
import pytest
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.batch.ensemble import (
    _aggregate,
    _vote_chunk,
    bootstrap_corr,
    bootstrap_pc,
)
from repro.batch.scan_pc import (
    pc_scan,
    pc_scan_batch,
    plan_n_prime,
    plan_schedule,
    scan_levels_batch,
)
from repro.core import engines
from repro.core.cit import correlation_from_samples
from repro.core.orient import (
    cpdag_from_skeleton,
    cpdag_np,
    sepset_membership,
)
from repro.core.pc import pc, pc_from_corr
from repro.data.synthetic_dag import oracle_pc_stable, sample_gaussian_dag

pytestmark = pytest.mark.batch


def _corr(n, m, density, seed):
    x, _ = sample_gaussian_dag(n=n, m=m, density=density, seed=seed)
    return correlation_from_samples(jnp.asarray(x))


# ---------------------------------------------------- B=1 parity vs S engine
@pytest.mark.parametrize(
    "n,density,seed", [(15, 0.2, 0), (20, 0.15, 1), (18, 0.3, 3), (25, 0.1, 2)]
)
def test_scan_b1_bit_identical_to_s_engine(n, density, seed):
    """ISSUE-2 acceptance: pc_scan reproduces the "S" engine's skeleton AND
    sepsets bit-identically up to the static level cap."""
    m = 3000
    c = _corr(n, m, density, seed)
    s_run = pc_from_corr(c, m, alpha=0.01, engine="S", max_level=3)
    res = pc_scan(c, m, alpha=0.01, max_level=3)
    assert bool(res.ok)
    np.testing.assert_array_equal(np.asarray(res.adj), s_run.adj)
    np.testing.assert_array_equal(np.asarray(res.sepsets), s_run.sepsets)
    np.testing.assert_array_equal(np.asarray(res.cpdag), s_run.cpdag)


def test_scan_engine_registry_wiring():
    """engine="scan" routes pc()/pc_from_corr() through the traced path and
    produces the same PCRun results as the S engine at the same cap."""
    m = 2500
    c = _corr(16, m, 0.2, 5)
    s_run = pc_from_corr(c, m, engine="S", max_level=3)
    run = pc_from_corr(c, m, engine="scan", max_level=3)
    np.testing.assert_array_equal(run.adj, s_run.adj)
    np.testing.assert_array_equal(run.sepsets, s_run.sepsets)
    np.testing.assert_array_equal(run.cpdag, s_run.cpdag)
    assert all(st_["engine"] == "scan" for st_ in run.level_stats)
    assert run.levels_run == s_run.levels_run  # true levels, not the cap
    assert run.sepset_dict() == s_run.sepset_dict()

    x, _ = sample_gaussian_dag(n=14, m=2000, density=0.2, seed=6)
    run_x = pc(x, engine="scan", max_level=2)
    ref_x = pc(x, engine="S", max_level=2)
    np.testing.assert_array_equal(run_x.adj, ref_x.adj)

    # registry: "scan" is whole-run only — never a per-level engine
    assert engines.is_whole_run("scan") and engines.is_whole_run("SCAN")
    assert not engines.is_whole_run("S")
    assert "scan" in engines.ENGINE_NAMES
    with pytest.raises(ValueError):
        engines.resolve("scan", 1)


# ----------------------------------------------------- batched vs loop parity
def test_scan_batch_matches_single_loop_and_s_engine():
    m = 2000
    cs = jnp.stack([_corr(16, m, 0.2, seed) for seed in range(4)])
    schedule = plan_schedule(cs, m, max_level=2)
    batch = pc_scan_batch(cs, m, max_level=2, n_prime=schedule)
    assert batch.adj.shape == (4, 16, 16)
    assert bool(np.asarray(batch.ok).all())
    for b in range(4):
        single = pc_scan(cs[b], m, max_level=2, n_prime=schedule)
        s_run = pc_from_corr(cs[b], m, engine="S", max_level=2)
        np.testing.assert_array_equal(np.asarray(batch.adj[b]), np.asarray(single.adj))
        np.testing.assert_array_equal(
            np.asarray(batch.sepsets[b]), np.asarray(single.sepsets)
        )
        np.testing.assert_array_equal(np.asarray(batch.adj[b]), s_run.adj)
        np.testing.assert_array_equal(np.asarray(batch.sepsets[b]), s_run.sepsets)
        np.testing.assert_array_equal(np.asarray(batch.cpdag[b]), s_run.cpdag)


def test_scan_levels_batch_matches_one_program():
    """The level-synced driver and the one-program scan are the same
    algorithm — identical results, and the discovered schedule reproduces
    them through pc_scan_batch."""
    m = 2000
    cs = jnp.stack([_corr(18, m, 0.25, seed + 20) for seed in range(3)])
    res_sync, schedule = scan_levels_batch(cs, m, max_level=3)
    res_prog = pc_scan_batch(cs, m, max_level=3, n_prime=schedule)
    assert len(schedule) == 3
    np.testing.assert_array_equal(np.asarray(res_sync.adj), np.asarray(res_prog.adj))
    np.testing.assert_array_equal(
        np.asarray(res_sync.sepsets), np.asarray(res_prog.sepsets)
    )
    np.testing.assert_array_equal(
        np.asarray(res_sync.cpdag), np.asarray(res_prog.cpdag)
    )
    assert bool(np.asarray(res_prog.ok).all())


def test_scan_ok_flags_degree_capped_runs():
    """A too-narrow width schedule must flag (not silently corrupt) graphs
    whose live degree exceeds it; exact reruns stay available."""
    m = 2500
    c = _corr(20, m, 0.3, 7)
    exact = pc_scan(c, m, max_level=2)  # n_prime=None → exact bound
    assert bool(exact.ok)
    capped = pc_scan(c, m, max_level=2, n_prime=2)
    assert not bool(capped.ok)


def test_ok_levels_factorise_ok_and_back_the_retry_contract():
    """ScanResult.ok_levels is the per-level factorisation of ok, names the
    capped level, and re-running the flagged graph unconstrained yields the
    exact answer bit-identically (the serving layer's escalation relies on
    exactly this contract — see the ScanResult docstring)."""
    m = 2500
    c = _corr(20, m, 0.3, 7)
    capped = pc_scan(c, m, max_level=2, n_prime=2)
    ok_levels = np.asarray(capped.ok_levels)
    assert ok_levels.shape == (2,)
    assert bool(capped.ok) == bool(ok_levels.all()) is False
    retried = pc_scan(c, m, max_level=2, n_prime=None)
    exact = pc_scan(c, m, max_level=2)
    assert bool(retried.ok)
    np.testing.assert_array_equal(np.asarray(retried.adj), np.asarray(exact.adj))
    np.testing.assert_array_equal(np.asarray(retried.sepsets),
                                  np.asarray(exact.sepsets))


def test_taus_as_data_bit_identical_to_alpha():
    """Explicit per-level tau vectors (trace data) reproduce the
    (m, alpha)-derived run bit-for-bit — the contract that lets one
    compiled program serve every (m, alpha) of a shape."""
    from repro.batch.scan_pc import taus_for

    m = 2000
    c = _corr(16, m, 0.2, 5)
    base = pc_scan(c, m, alpha=0.03, max_level=2)
    via_taus = pc_scan(c, m, max_level=2, taus=taus_for(m, 0.03, 2))
    np.testing.assert_array_equal(np.asarray(base.adj), np.asarray(via_taus.adj))
    np.testing.assert_array_equal(np.asarray(base.sepsets),
                                  np.asarray(via_taus.sepsets))


def test_mixed_alpha_batch_lanes_match_solo_runs():
    """One pc_scan_batch dispatch with per-lane tau vectors = the solo runs
    at each lane's alpha, bit-identically (mixed-alpha serving slots)."""
    from repro.batch.scan_pc import taus_for

    m = 2000
    c = _corr(16, m, 0.2, 6)
    alphas = (0.005, 0.05)
    taus = np.asarray([taus_for(m, a, 2) for a in alphas], np.float32)
    res = pc_scan_batch(jnp.stack([c, c]), m, max_level=2,
                        n_prime=plan_n_prime(c, m, alpha=max(alphas)),
                        taus=taus)
    assert bool(np.asarray(res.ok).all())
    for k, a in enumerate(alphas):
        solo = pc_scan(c, m, alpha=a, max_level=2)
        np.testing.assert_array_equal(np.asarray(res.adj[k]),
                                      np.asarray(solo.adj))
        np.testing.assert_array_equal(np.asarray(res.sepsets[k]),
                                      np.asarray(solo.sepsets))


def test_alpha_sweep_reuses_one_corr_lane_parity():
    """ISSUE-6 satellite (ROADMAP alpha-sweep follow-on): alpha_sweep over
    ONE correlation matrix is exact (ok all True via planning at the
    loosest alpha) and every lane is bit-identical to its solo pc_scan."""
    from repro.batch.scan_pc import alpha_sweep

    m = 2500
    c = _corr(18, m, 0.25, 8)
    alphas = (0.001, 0.01, 0.1)
    res = alpha_sweep(c, m, alphas, max_level=2)
    assert bool(np.asarray(res.ok).all())
    for k, a in enumerate(alphas):
        solo = pc_scan(c, m, alpha=a, max_level=2)
        np.testing.assert_array_equal(np.asarray(res.adj[k]),
                                      np.asarray(solo.adj))
        np.testing.assert_array_equal(np.asarray(res.sepsets[k]),
                                      np.asarray(solo.sepsets))
        np.testing.assert_array_equal(np.asarray(res.cpdag[k]),
                                      np.asarray(solo.cpdag))


def test_plan_n_prime_bounds_level0_degree():
    m = 2000
    cs = jnp.stack([_corr(16, m, 0.25, seed) for seed in range(3)])
    npr = plan_n_prime(cs, m)
    from repro.core.cit import threshold
    from repro.core.levels import level0

    degs = [int(jnp.max(jnp.sum(level0(c, threshold(m, 0, 0.01)), axis=1)))
            for c in cs]
    assert npr >= max(degs)
    assert npr <= 16


# ------------------------------------------------------------------ ensemble
def test_bootstrap_ensemble_invariants_and_reproducibility():
    x, _ = sample_gaussian_dag(n=14, m=1000, density=0.15, seed=2)
    run = bootstrap_pc(x, n_boot=8, alpha=0.01, max_level=2, seed=0)
    n = 14
    assert run.replicate_adj.shape == (8, n, n)
    assert run.replicate_ok.shape == (8,) and run.replicate_ok.all()
    assert run.edge_freq.min() >= 0.0 and run.edge_freq.max() <= 1.0
    np.testing.assert_array_equal(run.edge_freq, run.edge_freq.T)
    # stability selection is exactly freq >= threshold (off-diagonal)
    expect = (run.edge_freq >= run.stability_threshold) & ~np.eye(n, dtype=bool)
    np.testing.assert_array_equal(run.adj, expect)
    # orientation only drops directions: undirected closure == skeleton
    np.testing.assert_array_equal(run.cpdag | run.cpdag.T, run.adj)
    # every replicate is a valid skeleton
    for b in range(8):
        rep = run.replicate_adj[b]
        np.testing.assert_array_equal(rep, rep.T)
        assert not rep.diagonal().any()

    # explicit key threading → bit-reproducible
    run2 = bootstrap_pc(x, n_boot=8, alpha=0.01, max_level=2, seed=0)
    np.testing.assert_array_equal(run.edge_freq, run2.edge_freq)
    np.testing.assert_array_equal(run.cpdag, run2.cpdag)
    # a different seed resamples differently (probability ~1)
    run3 = bootstrap_pc(x, n_boot=8, alpha=0.01, max_level=2, seed=1)
    assert not np.array_equal(run.replicate_adj, run3.replicate_adj)


def test_aggregate_vote_chunking_bit_identical():
    """Satellite: the sepset-vote aggregation chunks its (b, n, n, n)
    membership tensor over the replicate axis under a byte cap instead of
    materialising all B at once — integer vote counts accumulate across
    chunks, so every chunking (including the degenerate 1-replicate steps
    used at large n) must reproduce the unchunked result bit-for-bit."""
    import jax

    x, _ = sample_gaussian_dag(n=13, m=900, density=0.2, seed=6)
    keys = jax.random.split(jax.random.PRNGKey(3), 7)
    cs = bootstrap_corr(x, keys, corr="jnp")
    res, _ = scan_levels_batch(cs, x.shape[0], max_level=2, orient=False)

    ref = [np.asarray(o) for o in
           _aggregate(res.adj, res.sepsets, 0.5, vote_chunk=None)]
    for chunk in (1, 2, 3, 7, 64):
        got = _aggregate(res.adj, res.sepsets, 0.5, vote_chunk=chunk)
        for r, g in zip(ref, got):
            np.testing.assert_array_equal(r, np.asarray(g))

    # the budget-derived chunk: n³ bool bytes per replicate under the cap
    assert _vote_chunk(32, 100) == 32          # tiny graphs: all at once
    assert _vote_chunk(32, 1000) == 1          # n≈1000: one replicate/step
    assert 1 <= _vote_chunk(32, 500) < 32
    # bootstrap_pc routes through the chunked path and stays reproducible
    e1 = bootstrap_pc(x, n_boot=5, max_level=2, seed=0)
    e2 = bootstrap_pc(x, n_boot=5, max_level=2, seed=0)
    np.testing.assert_array_equal(e1.cpdag, e2.cpdag)


def test_bootstrap_thresholds_nest():
    """Higher stability thresholds select nested sub-skeletons."""
    x, _ = sample_gaussian_dag(n=12, m=800, density=0.2, seed=4)
    loose = bootstrap_pc(x, n_boot=6, max_level=2, seed=0, stability_threshold=0.25)
    strict = bootstrap_pc(x, n_boot=6, max_level=2, seed=0, stability_threshold=0.75)
    assert not (strict.adj & ~loose.adj).any()
    np.testing.assert_array_equal(loose.edge_freq, strict.edge_freq)


def test_bootstrap_corr_validates_and_shapes():
    x, _ = sample_gaussian_dag(n=10, m=500, density=0.2, seed=3)
    import jax

    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    cs = bootstrap_corr(x, keys, corr="jnp")
    assert cs.shape == (5, 10, 10)
    cs_np = np.asarray(cs)
    np.testing.assert_allclose(cs_np, np.swapaxes(cs_np, 1, 2), atol=1e-6)
    np.testing.assert_allclose(cs_np[:, np.arange(10), np.arange(10)], 1.0)
    with pytest.raises(ValueError):
        bootstrap_corr(x, keys, corr="mxu")


# ------------------------------------------- orientation property vs oracle
@given(st.integers(6, 11), st.floats(0.15, 0.4), st.integers(0, 10_000))
@settings(max_examples=15, deadline=None)
def test_property_cpdag_matches_serial_oracle(n, density, seed):
    """cpdag_from_skeleton == cpdag_np on random sparse skeletons+sepsets
    (generated consistently via the d-separation oracle on random DAGs), at
    the default neighbour-list width (n) and at the tightest one (the max
    degree, as the host drivers bound it)."""
    _, dag = sample_gaussian_dag(n=n, m=10, density=density, seed=seed)
    adj_o, sep_o = oracle_pc_stable(dag)
    cp_ref = cpdag_np(adj_o, sep_o)
    sep = -np.ones((n, n, 8), np.int32)
    for (i, j), s in sep_o.items():
        sep[i, j, : len(s)] = s
        sep[j, i, : len(s)] = s
    tight = max(int(adj_o.sum(axis=1).max()), 1)
    for n_prime in (None, tight):
        cp_jax = np.asarray(cpdag_from_skeleton(
            jnp.asarray(adj_o), jnp.asarray(sep), n_prime=n_prime))
        np.testing.assert_array_equal(cp_jax, cp_ref)


def test_sepset_membership_matches_bruteforce():
    rng = np.random.default_rng(0)
    n = 9
    sep = rng.integers(-2, n, size=(n, n, 4)).astype(np.int32)
    got = np.asarray(sepset_membership(jnp.asarray(sep)))
    for i in range(n):
        for j in range(n):
            for k in range(n):
                assert got[i, j, k] == (k in sep[i, j].tolist())


# ------------------------------------------------- vectorised sepset_dict
def test_sepset_dict_matches_bruteforce_reference():
    m = 2500
    c = _corr(18, m, 0.25, 11)
    run = pc_from_corr(c, m, alpha=0.01, engine="S")

    # the pre-vectorisation reference implementation
    ref = {}
    n = run.adj.shape[0]
    for i in range(n):
        for j in range(i + 1, n):
            s = run.sepsets[i, j]
            s = tuple(int(v) for v in s[s >= 0])
            if not run.adj[i, j] and (s or run.sepsets[i, j, 0] != -2):
                ref[(i, j)] = s
    assert run.sepset_dict() == ref
    assert len(ref) > 0
