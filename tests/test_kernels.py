"""Per-kernel interpret-mode validation against the pure-jnp oracles in
kernels/ref.py, swept over shapes/dtypes, plus an end-to-end engine test."""
import numpy as np
import pytest
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

pytestmark = pytest.mark.kernels

RNG = np.random.default_rng(42)


def _corr_inputs(m, n, dtype):
    return RNG.normal(size=(m, n)).astype(dtype)


# ------------------------------------------------------------------- corr
@pytest.mark.parametrize("m,n", [(64, 32), (300, 70), (512, 256), (1000, 300), (100, 257)])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_corr_kernel_matches_ref(m, n, dtype):
    x = _corr_inputs(m, n, dtype)
    got = np.asarray(ops.correlation(jnp.asarray(x)))
    want = np.asarray(ref.corr_ref(jnp.asarray(x)))
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert got.dtype == np.float32


# ----------------------------------------------------------------- level 0
@pytest.mark.parametrize("n", [16, 100, 256, 300])
@pytest.mark.parametrize("tau", [0.01, 0.1, 0.5])
def test_level0_kernel_matches_ref(n, tau):
    c = np.clip(RNG.normal(0, 0.4, size=(n, n)), -0.99, 0.99).astype(np.float32)
    c = (c + c.T) / 2
    np.fill_diagonal(c, 1.0)
    got = np.asarray(ops.level0(jnp.asarray(c), tau))
    want = np.asarray(ref.level0_ref(jnp.asarray(c), tau))
    np.testing.assert_array_equal(got, want)


# ----------------------------------------------------------------- level 1
@pytest.mark.parametrize("n", [16, 64, 130, 256])
@pytest.mark.parametrize("tau", [0.02, 0.2])
def test_level1_kernel_matches_ref(n, tau):
    c = np.clip(RNG.normal(0, 0.35, size=(n, n)), -0.99, 0.99).astype(np.float32)
    c = (c + c.T) / 2
    np.fill_diagonal(c, 1.0)
    adj = (RNG.random((n, n)) < 0.4)
    adj = np.triu(adj, 1)
    adj = adj | adj.T
    rem_k, kwin_k = ops.level1_dense(jnp.asarray(c), jnp.asarray(adj), tau)
    rem_r, kwin_r = ref.level1_dense_ref(jnp.asarray(c), jnp.asarray(adj), tau)
    np.testing.assert_array_equal(np.asarray(rem_k), np.asarray(rem_r))
    np.testing.assert_array_equal(np.asarray(kwin_k), np.asarray(kwin_r))


# ----------------------------------------- cholinv + cisweep (fused ci_shared)
@pytest.mark.parametrize("ell", [1, 2, 3, 4, 6, 8])
@pytest.mark.parametrize("b,p", [(64, 4), (500, 11), (1024, 16), (2048, 3)])
def test_ci_shared_matches_ref(ell, b, p):
    a = RNG.normal(size=(b, ell, ell)).astype(np.float32)
    m2 = a @ a.transpose(0, 2, 1) + 0.5 * np.eye(ell, dtype=np.float32)
    ci_s = (RNG.normal(size=(b, ell)) * 0.3).astype(np.float32)
    cj_s = (RNG.normal(size=(b, p, ell)) * 0.3).astype(np.float32)
    cij = (RNG.normal(size=(b, p)) * 0.5).astype(np.float32)
    mask = RNG.random((b, p)) < 0.8
    tau = 0.2
    got = np.asarray(
        ops.ci_shared(jnp.asarray(m2), jnp.asarray(ci_s), jnp.asarray(cj_s),
                      jnp.asarray(cij), jnp.asarray(mask), tau, ell=ell)
    )
    g, u, var = ref.cholinv_ref(jnp.asarray(m2), jnp.asarray(ci_s))
    want = np.asarray(
        ref.cisweep_ref(g, u, var, jnp.asarray(cj_s), jnp.asarray(cij),
                        jnp.asarray(mask), tau)
    )
    assert (got != want).sum() == 0


@given(st.integers(1, 5), st.integers(1, 200), st.integers(1, 9), st.integers(0, 10_000))
@settings(max_examples=20, deadline=None)
def test_ci_shared_property(ell, b, p, seed):
    """Property: kernel decision == oracle decision for random SPD batches."""
    rng = np.random.default_rng(seed)
    a = rng.normal(size=(b, ell, ell)).astype(np.float32)
    m2 = a @ a.transpose(0, 2, 1) + np.eye(ell, dtype=np.float32)
    ci_s = (rng.normal(size=(b, ell)) * 0.2).astype(np.float32)
    cj_s = (rng.normal(size=(b, p, ell)) * 0.2).astype(np.float32)
    cij = (rng.normal(size=(b, p)) * 0.4).astype(np.float32)
    mask = np.ones((b, p), bool)
    tau = float(rng.uniform(0.05, 0.5))
    got = np.asarray(
        ops.ci_shared(jnp.asarray(m2), jnp.asarray(ci_s), jnp.asarray(cj_s),
                      jnp.asarray(cij), jnp.asarray(mask), tau, ell=ell)
    )
    g, u, var = ref.cholinv_ref(jnp.asarray(m2), jnp.asarray(ci_s))
    want = np.asarray(
        ref.cisweep_ref(g, u, var, jnp.asarray(cj_s), jnp.asarray(cij),
                        jnp.asarray(mask), tau)
    )
    # borderline |z - tau| < 1e-5 cells may flip under fp reassociation
    g2 = np.asarray(ref.cisweep_ref(g, u, var, jnp.asarray(cj_s), jnp.asarray(cij),
                                    jnp.asarray(mask), tau + 1e-4))
    g3 = np.asarray(ref.cisweep_ref(g, u, var, jnp.asarray(cj_s), jnp.asarray(cij),
                                    jnp.asarray(mask), tau - 1e-4))
    disagree = got != want
    assert (disagree & ~(g2 != g3)).sum() == 0


# -------------------------------------------------- end-to-end kernel engine
def test_pc_with_kernel_engine_matches_pure_jax():
    from repro.core.pc import pc
    from repro.kernels.ops import chunk_s_kernel
    from repro.data.synthetic_dag import sample_gaussian_dag

    x, _ = sample_gaussian_dag(n=18, m=3000, density=0.25, seed=9)
    base = pc(x, engine="S")
    kern = pc(x, engine="S", chunk_fn_s=chunk_s_kernel)
    np.testing.assert_array_equal(base.adj, kern.adj)
    np.testing.assert_array_equal(base.sepsets, kern.sepsets)
    np.testing.assert_array_equal(base.cpdag, kern.cpdag)


# -------------------------------------------------------------------- gsq
@pytest.mark.parametrize("r,q,m,b", [
    (2, 1, 100, 50),      # level 0, binary
    (3, 1, 257, 130),     # level 0, ternary, unaligned shapes
    (2, 2, 300, 200),     # level 1
    (3, 9, 640, 128),     # level 2, ternary (K = 81)
    (4, 4, 64, 300),      # wide-B, level 1, quaternary
])
def test_gsq_cells_matches_ref_bitwise(r, q, m, b):
    """The Pallas G² histogram kernel must be BITWISE equal to the jnp
    reference: counts are exact integers in fp32 and both reduce through
    the same deterministic fold (kernels/gsq.py docstring contract)."""
    from repro.kernels import gsq

    rng = np.random.default_rng(r * 1000 + q)
    k = q * r * r
    jc = rng.integers(0, k, size=(m, b)).astype(np.int32)
    jc[rng.random(size=jc.shape) < 0.1] = -1  # padding lanes
    got = np.asarray(gsq.gsq_cells(jnp.asarray(jc), r=r, q=q))
    want = np.asarray(gsq.gsq_ref(jnp.asarray(jc), r=r, q=q))
    np.testing.assert_array_equal(got, want)  # bitwise, not allclose
    assert got.dtype == np.float32


def test_gsq_known_value():
    """Hand-checked 2×2 table: N = [[30, 10], [10, 30]] over 80 samples."""
    from scipy.stats import chi2_contingency

    from repro.kernels import gsq

    tab = np.array([[30, 10], [10, 30]])
    codes = np.repeat(np.arange(4), tab.flatten())  # jc = a*2 + b
    g2 = float(gsq.gsq_ref(jnp.asarray(codes[:, None], jnp.int32), r=2, q=1)[0])
    want = chi2_contingency(tab, correction=False, lambda_="log-likelihood").statistic
    assert g2 == pytest.approx(want, rel=1e-5)


# ------------------------------------------------ S-grid over slot blocks
@pytest.mark.parametrize("ell", [2, 3])
def test_sgrid_slot_blocks_match_jnp_sweep(ell):
    """A row wider than one slot block (kernels/sgrid.slot_block) runs as
    several blocks on the grid, with the tail slots padded — the winners
    must equal the jnp sweep's (levels.ci_sweep) min separating rank."""
    from repro.core import levels as L
    from repro.core.cit import correlation_from_samples, threshold
    from repro.core.compact import compact_rows
    from repro.kernels import sgrid

    n, m, t = 80, 200, 16
    x = RNG.normal(size=(m, n))
    x[:, 1:] += 0.5 * x[:, :-1]
    c = correlation_from_samples(jnp.asarray(x))
    adj = ~jnp.eye(n, dtype=bool)
    compact, counts = compact_rows(adj, n_prime=n)
    assert sgrid.slot_block(n, ell) < n
    ranks = jnp.arange(t, dtype=jnp.int32)
    gathered = L.gather_s(c, adj, compact, counts, jnp.arange(n, dtype=jnp.int32),
                          ranks, ell=ell, n_max=n)
    tau = threshold(m, ell, 0.01)
    t_loc, s_win = ops.ci_shared_grid(*gathered, tau, ell=ell)

    found = np.asarray(L.ci_sweep(*gathered[:5], tau, ell=ell))  # (n, T, n′)
    first = found.argmax(axis=1)
    want_t = np.where(found.any(axis=1), first, sgrid.SENTINEL)
    np.testing.assert_array_equal(np.asarray(t_loc), want_t)
    s_ids = np.asarray(gathered[5])  # (n, T, ℓ)
    want_s = np.take_along_axis(s_ids, first[:, :, None].clip(0, t - 1), axis=1)
    hit = found.any(axis=1)
    assert hit.any() and not hit.all()
    np.testing.assert_array_equal(np.asarray(s_win)[hit], want_s[hit])
