"""The benchmark's data and its plain reference, on the CPU.

* The benchmark's generator gives full-rank, non-collinear samples at the
  published Table-1 sizes; the repository's own generator, which
  standardises only at the end, does not at DREAM5-Insilico's size.
* The plain PC-stable reference equals the program's jnp engine on
  skeleton, sepsets and CPDAG at n of about 60, and its check reads a
  seeded one-edge corruption as wrong.
* The float32 level-1 screen agrees with the float64 rows within its
  stated error bound.
"""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.data.gaussian_dag import sample  # noqa: E402
from bench.reference import orient, pc_stable  # noqa: E402

NCI60 = (1190, 47, 0.02)
DREAM5 = (1643, 850, 0.05)


def _corr_stats(x):
    c = np.corrcoef(x.T)
    np.fill_diagonal(c, 0.0)
    rank = np.linalg.matrix_rank(x - x.mean(axis=0))
    return np.abs(c), rank


@pytest.mark.parametrize("seed", [0, 1])
def test_generator_nci60_size_not_collinear(seed):
    n, m, d = NCI60
    ac, rank = _corr_stats(sample(n, m, d, seed))
    assert ac.max() <= 0.99
    assert rank == m - 1


@pytest.mark.parametrize("seed", [0, 1])
def test_generator_dream5_size_full_rank(seed):
    n, m, d = DREAM5
    ac, rank = _corr_stats(sample(n, m, d, seed))
    assert ac.max() <= 0.9999
    assert rank == m - 1


def test_repo_generator_is_collinear_at_dream5_size():
    from repro.data.synthetic_dag import sample_gaussian_dag

    n, m, d = DREAM5
    x, _ = sample_gaussian_dag(n, m, d, seed=0)
    ac = np.abs(np.corrcoef(x.T))
    off = ~np.eye(n, dtype=bool)
    assert (ac[off] > 0.9999).mean() > 0.5


def _program(x, **kw):
    from repro.core import pc

    return pc(np.float32(x), 0.01, engine="S", corr="jnp", **kw)


CASES = [(60, 200, 0.1, None), (60, 40, 0.1, None), (60, 500, 0.15, None), (60, 30, 0.2, 2)]


@pytest.mark.parametrize("case", range(len(CASES)))
def test_reference_equals_program(case):
    n, m, d, max_level = CASES[case]
    x = sample(n, m, d, 100 + case)
    run = _program(x, max_level=max_level)
    c = pc_stable.correlation(np.float32(x).astype(np.float64))
    adj, sep = pc_stable.pc_stable(c, m, 0.01, max_level=max_level)
    assert (adj == run.adj).all()
    assert (sep == run.sepsets).all()
    assert (orient.cpdag(run.adj, run.sepsets) == run.cpdag).all()
    assert pc_stable.check(c, m, 0.01, run.adj, run.sepsets, max_level=max_level) == {
        "z_gap": 0.0, "bad": 0}


@pytest.mark.parametrize("case", range(3))
def test_check_fails_on_one_edge_corruption(case):
    n, m, d, max_level = CASES[case]
    x = sample(n, m, d, 200 + case)
    run = _program(x, max_level=max_level)
    c = pc_stable.correlation(np.float32(x).astype(np.float64))
    adj, sep = np.array(run.adj), np.array(run.sepsets)
    edges = np.argwhere(np.triu(adj, 1))
    i, j = edges[np.random.default_rng(case).integers(len(edges))]
    adj[i, j] = adj[j, i] = False
    sep[i, j, 0] = sep[j, i, 0] = -2
    res = pc_stable.check(c, m, 0.01, adj, sep, max_level=max_level)
    assert res["z_gap"] > 0.05


def test_check_counts_a_foreign_sepset():
    n, m, d = 60, 200, 0.1
    x = sample(n, m, d, 7)
    run = _program(x)
    c = pc_stable.correlation(np.float32(x).astype(np.float64))
    sep = np.array(run.sepsets)
    lvl = pc_stable.removal_level(run.adj, sep)
    i, j = np.argwhere(np.triu(lvl == 1, 1))[0]
    # a variable neither endpoint had as a neighbour when level 1 started
    other = next(k for k in range(n) if k not in (i, j) and lvl[i, k] == 0 and lvl[j, k] == 0)
    sep[i, j, 0] = sep[j, i, 0] = other
    assert pc_stable.check(c, m, 0.01, run.adj, sep)["bad"] >= 1


def test_level1_screen_within_its_bound():
    n, m, d = 120, 300, 0.2
    x = sample(n, m, d, 3)
    c = pc_stable.correlation(x)
    dd = np.sqrt(np.maximum(1 - c * c, 0))
    g = ~np.eye(n, dtype=bool)
    lo, _ = pc_stable.level1_screen(c, dd, g, np.full((n, n), n), 0.0)
    exact = np.full((n, n), np.inf)
    for i in range(n):
        for r0, rho in pc_stable.row_rho_blocks(c, i, np.flatnonzero(g[i]), 1):
            exact[i, g[i]] = np.minimum(exact[i, g[i]], rho.min(axis=0))
    err = np.abs(pc_stable.z_of(np.asarray(lo, np.float64)) - pc_stable.z_of(exact))[g]
    assert err.max() < pc_stable.screen_margin(dd) / 10
