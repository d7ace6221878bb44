"""Engine registry: kernel-backed "auto" parity with the jnp S engine and
the serial oracle, npr-bucketing invariance + boundary behaviour, and the
compile-count probe for bucketed chunk planning."""
import math

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import engines, levels as L
from repro.core.cit import correlation_from_samples, fisher_z, partial_corr_single, threshold
from repro.core.pc import pc, pc_from_corr
from repro.core.stable_ref import pc_stable_skeleton
from repro.data.synthetic_dag import sample_gaussian_dag

pytestmark = pytest.mark.kernels


# ------------------------------------------------------------------ registry
def test_resolve_auto_hybrid():
    assert engines.resolve("auto", 1) == "L1-dense"
    assert engines.resolve("auto", 2) == "S-kernel"
    assert engines.resolve("auto", 5) == "S-kernel"
    assert engines.resolve("L1-dense", 1) == "L1-dense"
    assert engines.resolve("L1-dense", 2) == "S"  # dense cube is ℓ=1 only
    assert engines.resolve("s-kernel", 3) == "S-kernel"  # case-insensitive
    assert engines.resolve("s-grid", 1) == "S-grid"  # any level, grid-resident
    assert engines.resolve("S-grid", 4) == "S-grid"
    assert engines.resolve(lambda ell: "E" if ell == 1 else "S", 1) == "E"
    with pytest.raises(ValueError):
        engines.resolve("warp", 1)


# ------------------------------------------- end-to-end parity: auto == S == ref
@pytest.mark.parametrize(
    "n,density,alpha,seed",
    [(15, 0.2, 0.01, 0), (20, 0.15, 0.01, 1), (18, 0.3, 0.05, 3), (25, 0.1, 0.01, 2)],
)
def test_auto_engine_parity(n, density, alpha, seed):
    """engine="auto" (Pallas L1-dense + cholinv/cisweep) must produce the
    identical skeleton, sepsets and CPDAG as the jnp "S" engine, and the
    same skeleton as the serial PC-stable oracle."""
    m = 3000
    x, _ = sample_gaussian_dag(n=n, m=m, density=density, seed=seed)
    c = correlation_from_samples(jnp.asarray(x))
    ref = pc_stable_skeleton(np.asarray(c), m=m, alpha=alpha)
    s_run = pc_from_corr(c, m, alpha=alpha, engine="S")
    a_run = pc_from_corr(c, m, alpha=alpha, engine="auto")

    np.testing.assert_array_equal(a_run.adj, ref.adj)
    np.testing.assert_array_equal(a_run.adj, s_run.adj)
    np.testing.assert_array_equal(a_run.sepsets, s_run.sepsets)
    np.testing.assert_array_equal(a_run.cpdag, s_run.cpdag)

    # dispatch proof: the Pallas paths actually ran
    ran = {st["level"]: st["engine"] for st in a_run.level_stats if not st["skipped"]}
    assert ran.get(1) == "L1-dense"
    assert all(e == "S-kernel" for lvl, e in ran.items() if lvl >= 2)
    assert any(lvl >= 2 for lvl in ran), "no ℓ≥2 level exercised the cisweep path"


def test_auto_sepsets_certify_removals():
    """Every sepset the kernel engines record must pass the CI test it
    claims (certification, not just agreement)."""
    m = 3000
    x, _ = sample_gaussian_dag(n=18, m=m, density=0.25, seed=11)
    c = correlation_from_samples(jnp.asarray(x))
    run = pc_from_corr(c, m, alpha=0.01, engine="auto")
    n = run.adj.shape[0]
    checked = 0
    for i in range(n):
        for j in range(i + 1, n):
            s = run.sepsets[i, j]
            if run.adj[i, j] or s[0] == -2:
                continue
            ids = s[s >= 0]
            if len(ids) == 0:
                continue
            rho = partial_corr_single(c, i, j, jnp.asarray(ids))
            assert float(fisher_z(rho)) <= threshold(m, len(ids), 0.01), (i, j, ids)
            checked += 1
    assert checked > 0


def test_pc_corr_kernel_path():
    """pc(x, corr="kernel") routes C through the tiled MXU kernel and still
    recovers the same skeleton as the jnp correlation."""
    x, _ = sample_gaussian_dag(n=16, m=2000, density=0.25, seed=5)
    base = pc(x, alpha=0.01, engine="S", corr="jnp")
    kern = pc(x, alpha=0.01, engine="S", corr="kernel")
    np.testing.assert_array_equal(base.adj, kern.adj)
    with pytest.raises(ValueError):
        pc(x, corr="mxu")


# ------------------------------------------------- grid-resident engine parity
@pytest.mark.parametrize(
    "n,density,alpha,seed",
    [(15, 0.2, 0.01, 0), (18, 0.3, 0.05, 3)],  # the deep fixture runs ℓ=2..5
)
def test_grid_engine_bit_parity(n, density, alpha, seed):
    """ISSUE-5 acceptance: engine="S-grid" (rank axis in the Pallas grid,
    winners accumulated in VMEM across grid steps, commit fused per launch)
    must produce bit-identical skeleton, sepsets AND CPDAG to the jnp "S"
    engine across every level the fixture reaches — with host dispatches
    per level reduced to 1 (asserted via the level-stats dispatch counter)."""
    m = 3000
    x, _ = sample_gaussian_dag(n=n, m=m, density=density, seed=seed)
    c = correlation_from_samples(jnp.asarray(x))
    s_run = pc_from_corr(c, m, alpha=alpha, engine="S")
    g_run = pc_from_corr(c, m, alpha=alpha, engine="S-grid")

    np.testing.assert_array_equal(g_run.adj, s_run.adj)
    np.testing.assert_array_equal(g_run.sepsets, s_run.sepsets)
    np.testing.assert_array_equal(g_run.cpdag, s_run.cpdag)

    ran = [st for st in g_run.level_stats if not st["skipped"]]
    assert ran and all(st["engine"] == "S-grid" for st in ran)
    assert all(st["dispatches"] == 1 for st in ran), [
        (st["level"], st["dispatches"]) for st in ran
    ]
    assert any(st["level"] >= 2 for st in ran), "no ℓ≥2 level exercised"
    # the chunked S engine dispatched once per chunk — strictly more overall
    s_disp = sum(st["dispatches"] for st in s_run.level_stats if not st["skipped"])
    assert s_disp >= len(ran)


def test_registry_counts_agree_with_level_stats_across_engines():
    """Counter-drift guard (ISSUE-7): dispatches/chunks used to be bumped
    in three unrelated places; obs.record_level_stats at the
    engines.run_level seam is now the single definition, so the metrics
    registry totals must equal the summed per-level stats dicts — for
    every engine, including the paths that overwrite st["engine"]."""
    from repro import obs

    m = 2000
    x, _ = sample_gaussian_dag(n=16, m=m, density=0.2, seed=4)
    c = correlation_from_samples(jnp.asarray(x))
    for engine in ("S", "E", "S-grid", "auto"):
        with obs.scoped(enabled=True), obs.scoped_registry() as reg:
            run = pc_from_corr(c, m, alpha=0.01, engine=engine)
            want_disp = sum(st["dispatches"] for st in run.level_stats)
            want_chunks = sum(st.get("chunks", 0) for st in run.level_stats)
            assert reg.total(obs.DISPATCHES) == want_disp, engine
            assert reg.total(obs.CHUNKS) == want_chunks, engine
            assert reg.total(obs.LEVELS) == len(run.level_stats), engine
            # labels carry the CONCRETE engine names (auto resolves per level)
            for st in run.level_stats:
                assert reg.value(obs.LEVELS, engine=st["engine"],
                                 level=st["level"], layout="single") >= 1


def test_grid_engine_multi_launch_parity():
    """A launch budget too small for one level forces several grid launches;
    ranks ascend across launches and each launch fuses its own commit, so
    results stay bit-identical to the chunked engine (the same argument as
    chunked dispatch — first separating chunk wins)."""
    m = 2000
    x, _ = sample_gaussian_dag(n=22, m=m, density=0.25, seed=9)
    c = correlation_from_samples(jnp.asarray(x))
    s_run = pc_from_corr(c, m, engine="S", cell_budget=2**10)
    g_run = pc_from_corr(c, m, engine="S-grid", cell_budget=2**10)
    assert any(st["chunks"] > 1 for st in g_run.level_stats
               if not st["skipped"]), "budget did not force multi-launch"
    np.testing.assert_array_equal(g_run.adj, s_run.adj)
    np.testing.assert_array_equal(g_run.sepsets, s_run.sepsets)
    np.testing.assert_array_equal(g_run.cpdag, s_run.cpdag)


# ------------------------------------- degree-class worklists (S-kernel, ℓ≥2)
def _class_graph(kind: str, n: int = 48, seed: int = 0) -> np.ndarray:
    """"hub": row 0 has 40 neighbours, every other row 1-8; "uniform": a
    circulant graph, every row of degree 6 (one n′ bucket)."""
    rng = np.random.default_rng(seed)
    adj = np.zeros((n, n), bool)
    if kind == "hub":
        adj[0, 1:41] = True
        for i in range(1, n):
            adj[i, rng.choice(np.arange(41, n) if i < 41 else np.arange(1, n),
                              size=rng.integers(1, 4), replace=False)] = True
    else:
        for k in (1, 2, 3):
            adj[np.arange(n), (np.arange(n) + k) % n] = True
    adj = adj | adj.T
    np.fill_diagonal(adj, False)
    return adj


@pytest.mark.parametrize("ell", [2, 3])
@pytest.mark.parametrize("kind,cell_budget,bucket", [
    ("hub", L.DEFAULT_CELL_BUDGET, True),
    ("hub", 2**20, True),  # the hub class takes several programs
    ("uniform", L.DEFAULT_CELL_BUDGET, True),
    ("hub", L.DEFAULT_CELL_BUDGET, False),  # exact widths, still classed
], ids=["hub", "hub_chunked", "uniform", "hub_exact"])
def test_degree_classes_bit_parity(kind, cell_budget, bucket, ell):
    """The S-kernel level as degree-class worklists commits the same
    skeleton and sepsets, bit for bit, as the level-wide jnp S plan; it
    launches no more (row, set, neighbour) cells than that plan, strictly
    fewer where the degrees are skewed; and its programs' jit keys come
    from the bounded set (power-of-two rows and ranks, bucketed widths).
    Without bucketing the level is classed all the same, at each class's
    exact width."""
    n, m = 48, 300
    x, _ = sample_gaussian_dag(n=n, m=m, density=0.1, seed=3)
    c = jnp.asarray(np.asarray(correlation_from_samples(jnp.asarray(x))))
    adj = _class_graph(kind, n)
    # the sepsets as level 0 leaves them: removed edges carry -2 in slot 0
    sep = jnp.full((n, n, 8), -1, jnp.int32).at[:, :, 0].set(
        jnp.where(jnp.asarray(adj), -1, -2))
    tau = threshold(m, ell, 0.01)
    a_s, s_s, st_s = L.run_level(c, jnp.asarray(adj), sep, ell, tau,
                                 cell_budget=cell_budget, bucket=bucket)
    a_k, s_k, st_k = engines.run_level(c, jnp.asarray(adj), sep, ell, tau,
                                       engine="auto", cell_budget=cell_budget,
                                       bucket=bucket)
    assert np.asarray(adj & ~np.asarray(a_s)).any(), "the level removed nothing"
    np.testing.assert_array_equal(np.asarray(a_k), np.asarray(a_s))
    np.testing.assert_array_equal(np.asarray(s_k), np.asarray(s_s))

    classes = st_k["classes"]
    uniform = n * st_s["chunks"] * st_s["n_chunk"] * st_s["npr_bucket"]
    assert st_k["launched_tests"] == sum(
        k["rows_bucket"] * k["chunks"] * k["n_chunk"] * k["bucket"] for k in classes)
    if kind == "hub":
        assert st_k["launched_tests"] < uniform
        assert len(classes) >= 3
        assert sum(k["rows"] for k in classes) == int((adj.sum(1) > ell).sum())
    else:  # one bucket: the level-wide plan itself
        assert len(classes) == 1 and st_k["launched_tests"] == uniform
    if cell_budget < L.DEFAULT_CELL_BUDGET:
        assert max(k["chunks"] for k in classes) >= 2
    assert st_k["chunks"] == sum(k["chunks"] for k in classes)
    assert st_k["dispatches"] == st_k["chunks"] + 1
    degrees = adj.sum(1)
    for k in classes:
        rb, t, b = k["rows_bucket"], k["n_chunk"], k["bucket"]
        assert rb == n or (rb >= L.CLASS_ROW_FLOOR and rb & (rb - 1) == 0), k
        assert k["rows"] <= rb and k["chunks"] == -(-k["total"] // t)
        if bucket:
            assert t & (t - 1) == 0 and b == min(L.bucket_npr(b), n), k
            top = max(d for d in degrees if d > ell and min(L.bucket_npr(int(d)), n) == b)
        else:  # the exact width: the largest degree among the class's rows
            assert b in degrees, k
            top = b
        assert k["total"] == math.comb(int(top), ell), k  # the class's own rank range


def test_plan_classes_never_launch_more_than_the_level_plan():
    """Over random degree profiles the class plan launches at most the
    cells of one level-wide plan over all n rows (it falls back to that
    plan where power-of-two rounding would cost more)."""
    rng = np.random.default_rng(0)
    for trial in range(200):
        n = int(rng.integers(8, 400))
        ell = int(rng.integers(2, 4))
        counts = rng.integers(0, max(ell + 2, n // int(rng.integers(1, 8))), size=n)
        counts = np.minimum(counts, n - 1)
        if counts.max() - 1 < ell:
            continue
        budget = int(2 ** rng.integers(12, 25))
        classes = L.plan_classes(counts, ell, cell_budget=budget)
        npr_b, n_chunk, total = L.plan_level(int(counts.max()), ell, n,
                                             cell_budget=budget, n_cols=n)
        uniform = n * -(-total // n_chunk) * n_chunk * npr_b
        assert sum(k.launched for k in classes) <= uniform, (trial, n, ell)
        rows = np.concatenate([k.rows for k in classes])
        rows = rows[rows >= 0]
        assert len(set(rows.tolist())) == rows.size  # classes are row-disjoint
        assert set(np.flatnonzero(counts > ell)) <= set(rows.tolist())


def test_plan_level_caps_and_rejects_unrepresentable_ranks():
    """Satellite: without x64, combo ranks live in int32 — plan_level must
    FAIL loudly (not alias ranks through the clipped binomial table) when a
    level's total rank count exceeds the dtype capacity, and cap n_chunk so
    every rank a chunk touches stays representable."""
    import math

    # a level whose C(n', l) is astronomically past any integer dtype
    with pytest.raises(ValueError, match="rank capacity"):
        L.plan_level(3000, 8, 3000)

    # near the capacity: totals fit, and the planned chunk keeps
    # total + n_chunk inside the key range (ranks commit as rank*2 + bit)
    imax = L._imax()
    npr, ell = 4000, 3
    total = math.comb(npr, ell)
    if total <= imax:  # x64 ranks: plans, and the chunk respects the cap
        _, n_chunk, _ = L.plan_level(npr, ell, 64)
        assert total + n_chunk <= imax
    else:  # int32 ranks: C(4000,3) ≈ 1.07e10 is unrepresentable → loud error
        with pytest.raises(ValueError, match="rank capacity"):
            L.plan_level(npr, ell, 64)


# ------------------------------------------------------------- npr bucketing
def test_bucket_npr_boundaries():
    assert [L.bucket_npr(v) for v in (1, 2, 3, 8, 9, 17, 127)] == [1, 2, 4, 8, 16, 32, 128]
    assert L.bucket_npr(128) == 128
    assert L.bucket_npr(129) == 256
    assert L.bucket_npr(300) == 384  # lane multiples above one lane


@pytest.mark.parametrize("hub_degree", [8, 9])  # just below / above a bucket edge
def test_run_level_bucket_boundary(hub_degree):
    """run_level with bucketing must return bit-identical (adj, sep) to the
    exact-shape plan when the max degree sits on either side of a bucket
    edge, while the static n′ snaps to the bucket."""
    rng = np.random.default_rng(0)
    n = 24
    x, _ = sample_gaussian_dag(n=n, m=1500, density=0.3, seed=13)
    c = jnp.asarray(np.asarray(correlation_from_samples(jnp.asarray(x))))
    # hub row 0 with exactly `hub_degree` neighbours + a sparse tail
    adj = rng.random((n, n)) < 0.15
    adj = np.triu(adj, 1)
    adj[0, :] = False
    adj[0, 1 : 1 + hub_degree] = True
    adj = adj | adj.T
    np.fill_diagonal(adj, False)
    max_deg = int(adj.sum(1).max())
    assert max_deg == hub_degree
    sep = jnp.full((n, n, 8), -1, jnp.int32)
    tau = threshold(1500, 2, 0.01)

    for ell in (1, 2):
        a_b, s_b, st_b = L.run_level(c, jnp.asarray(adj), sep, ell, tau, bucket=True)
        a_e, s_e, st_e = L.run_level(c, jnp.asarray(adj), sep, ell, tau, bucket=False)
        np.testing.assert_array_equal(np.asarray(a_b), np.asarray(a_e))
        np.testing.assert_array_equal(np.asarray(s_b), np.asarray(s_e))
        assert st_b["npr_bucket"] == L.bucket_npr(max_deg)
        assert st_e["npr_bucket"] == max_deg
        assert (st_b["n_chunk"] & (st_b["n_chunk"] - 1)) == 0  # power of two


def test_bucketing_reduces_chunk_compilations():
    """The whole point of bucketing: the (ℓ, n_chunk, n′) jit key of every
    chunk dispatch must recur across multi-level runs whose exact max-degrees
    differ, so the workload's distinct chunk_s compilations STRICTLY drop.
    Probed two ways: the stats' compile keys and the jit cache itself
    (chunk_s._cache_size) on the second run of each mode."""
    # dense-ish graphs → several levels with distinct non-power-of-two degrees;
    # seeds chosen so per-level exact n′ differ between runs but buckets agree
    cs = []
    for seed in (2, 6):
        x, _ = sample_gaussian_dag(n=41, m=900, density=0.35, seed=seed)
        cs.append(correlation_from_samples(jnp.asarray(x)))

    probe = getattr(L.chunk_s, "_cache_size", None)
    keys, new_compiles = {}, {}
    for bucket in (False, True):
        runs = []
        for i, c in enumerate(cs):
            before = probe() if probe else 0
            runs.append(pc_from_corr(c, 900, engine="S", bucket=bucket))
            if i == 1:  # compiles triggered by the SECOND run of this mode
                new_compiles[bucket] = (probe() if probe else 0) - before
        keys[bucket] = {
            st["compile_key"] for r in runs for st in r.level_stats if not st["skipped"]
        }
        if bucket:  # bucketing must not change results
            for r, c in zip(runs, cs):
                exact_r = pc_from_corr(c, 900, engine="S", bucket=False)
                np.testing.assert_array_equal(r.adj, exact_r.adj)
                np.testing.assert_array_equal(r.sepsets, exact_r.sepsets)

    assert len(keys[False]) >= 4, "workload too shallow to exercise the planner"
    assert len(keys[True]) < len(keys[False]), (keys[True], keys[False])
    if probe:
        assert new_compiles[True] < new_compiles[False], (
            f"2nd bucketed run compiled {new_compiles[True]} chunk_s variants, "
            f"2nd exact run compiled {new_compiles[False]}"
        )


# ----------------------------------------- (engine × test-object) parity matrix
def test_engine_matrix_gaussian_citest_bit_identity():
    """Every Gaussian engine must be bit-identical whether the CI math is
    reached implicitly (the pre-seam default) or through an explicit
    GaussianCITest — skeleton AND sepsets (the ISSUE's refactor guarantee)."""
    from repro.core.cit import GaussianCITest

    m = 2500
    x, _ = sample_gaussian_dag(n=20, m=m, density=0.25, seed=9)
    c = correlation_from_samples(jnp.asarray(x))
    t = GaussianCITest(m=m, alpha=0.01)
    for eng in ("S", "E", "S-kernel", "auto"):
        base = pc_from_corr(c, m, alpha=0.01, engine=eng)
        via = pc_from_corr(c, m, alpha=0.01, engine=eng, test=t)
        np.testing.assert_array_equal(base.adj, via.adj, err_msg=eng)
        np.testing.assert_array_equal(base.sepsets, via.sepsets, err_msg=eng)
        np.testing.assert_array_equal(base.cpdag, via.cpdag, err_msg=eng)


def test_engine_matrix_discrete_all_names_agree():
    """Discrete test × every admissible engine name: the generic names remap
    onto the G² engines (jnp and Pallas) and ALL agree bit-for-bit."""
    from repro.data.synthetic_dag import sample_discrete_dag

    x, _ = sample_discrete_dag(n=9, m=260, density=0.35, arity=3, seed=2)
    for k in range(x.shape[1]):  # validate rejects constant columns
        if len(np.unique(x[:, k])) < 2:
            x[0, k] = (x[1, k] + 1) % 3
    runs = {
        eng: pc(x, alpha=0.05, test="discrete", engine=eng, max_level=2)
        for eng in ("S", "E", "auto", "S-kernel", "G2", "G2-kernel")
    }
    ref = runs["G2"]
    for eng, r in runs.items():
        np.testing.assert_array_equal(ref.adj, r.adj, err_msg=eng)
        np.testing.assert_array_equal(ref.sepsets, r.sepsets, err_msg=eng)
    # dispatch proof: stats record the remapped engine names
    for eng, want in (("S", "G2"), ("auto", "G2-kernel")):
        ran = {s["level"]: s["engine"] for s in runs[eng].level_stats
               if not s.get("skipped")}
        assert all(e == want for e in ran.values()), (eng, ran)
