"""Synthetic linear-Gaussian DAG data — the paper's §5.6 generator.

"We first generate a random adjacency matrix A_G with independent
realizations of Bernoulli(d) in the lower triangle ... replace the ones by
independent U[0.1, 1] ... samples are generated as V_i = N_i + Σ_j A[i,j]·V_j"
plus a d-separation oracle for exact-CI testing of the full pipeline.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class GaussianDAG:
    weights: np.ndarray  # (n, n) lower-triangular weighted adjacency, W[i,j]: Vj -> Vi
    adj: np.ndarray  # boolean directed adjacency, adj[i,j] True iff Vj -> Vi

    @property
    def n(self) -> int:
        return self.weights.shape[0]

    def skeleton(self) -> np.ndarray:
        return self.adj | self.adj.T

    def parents(self, i: int) -> np.ndarray:
        return np.flatnonzero(self.adj[i])


def random_dag(n: int, density: float, rng: np.random.Generator) -> GaussianDAG:
    mask = np.tril(rng.random((n, n)) < density, k=-1)
    w = np.where(mask, rng.uniform(0.1, 1.0, (n, n)), 0.0)
    return GaussianDAG(weights=w, adj=mask)


def sample_gaussian_dag(
    n: int,
    m: int,
    density: float = 0.1,
    seed: int = 0,
    noise_std: float = 1.0,
):
    """Returns (x: (m, n) float64 samples, dag). Topological order =
    variable order.

    Each column is standardised (zero mean, unit variance) in float64, as
    expression panels are. The raw variances grow down the topological
    order (|x| ≈ 2e19 at the paper's DREAM5 size, n=1643), past the float32
    range the engines compute the correlation in; correlation is
    scale-invariant, so standardising changes nothing else.
    """
    rng = np.random.default_rng(seed)
    dag = random_dag(n, density, rng)
    noise = rng.normal(0.0, noise_std, size=(m, n))
    x = np.zeros((m, n))
    for i in range(n):
        x[:, i] = noise[:, i] + x[:, : i] @ dag.weights[i, :i]
    x -= x.mean(axis=0)
    x /= x.std(axis=0)
    return x, dag


def sample_discrete_dag(
    n: int,
    m: int,
    density: float = 0.2,
    arity: int = 3,
    seed: int = 0,
    concentration: float = 0.5,
):
    """Categorical samples from a random DAG with Dirichlet CPTs.

    Reuses :func:`random_dag` for the structure; each variable gets one
    conditional probability table per joint parent configuration, rows drawn
    Dirichlet(concentration) — a small concentration (< 1) makes rows peaky,
    i.e. strong detectable dependences for the G² test. Ancestral sampling
    in variable order (the generator's topological order). Returns
    (x: (m, n) int64 codes in [0, arity), dag).
    """
    rng = np.random.default_rng(seed)
    dag = random_dag(n, density, rng)
    x = np.zeros((m, n), dtype=np.int64)
    for i in range(n):
        ps = dag.parents(i)
        q = arity ** len(ps)
        cpt = rng.dirichlet([concentration] * arity, size=q)  # (q, arity)
        cfg = np.zeros(m, dtype=np.int64)
        for p in ps:  # MSB-first fold, same convention as the engines
            cfg = cfg * arity + x[:, p]
        u = rng.random(m)
        x[:, i] = (cpt[cfg].cumsum(axis=1) < u[:, None]).sum(axis=1)
    return x, dag


# ---------------------------------------------------------------------------
# d-separation oracle (exact CI) — used to validate the full PC pipeline:
# PC with a perfect CI oracle must recover the true CPDAG exactly.
# ---------------------------------------------------------------------------
def d_separated(dag: GaussianDAG, i: int, j: int, s: tuple[int, ...]) -> bool:
    """Bayes-ball reachability: True iff Vi ⟂ Vj | S in the DAG."""
    n = dag.n
    s_set = set(s)
    # ancestors of S (for collider opening)
    anc_of_s = set()
    stack = list(s_set)
    while stack:
        v = stack.pop()
        for p in np.flatnonzero(dag.adj[v]):  # parents of v
            if p not in anc_of_s:
                anc_of_s.add(int(p))
                stack.append(int(p))
    anc_or_s = anc_of_s | s_set

    # walk edges with direction: (node, came_from_child?) states
    # adj[i,j] True means Vj -> Vi:  children(v) = flatnonzero(adj[:, v])
    children = [np.flatnonzero(dag.adj[:, v]) for v in range(n)]
    parents = [np.flatnonzero(dag.adj[v]) for v in range(n)]

    visited = set()
    # (node, direction) direction: 'up' = arrived from a child (against arrow),
    # 'down' = arrived from a parent (along arrow)
    stack = [(i, "up")]
    while stack:
        node, direction = stack.pop()
        if (node, direction) in visited:
            continue
        visited.add((node, direction))
        if node == j:
            return False
        if direction == "up" and node not in s_set:
            for p in parents[node]:
                stack.append((int(p), "up"))
            for c in children[node]:
                stack.append((int(c), "down"))
        elif direction == "down":
            if node not in s_set:
                for c in children[node]:
                    stack.append((int(c), "down"))
            if node in anc_or_s:  # collider (or its descendant in S) opens
                for p in parents[node]:
                    stack.append((int(p), "up"))
    return True


def oracle_pc_stable(dag: GaussianDAG, max_level: int | None = None):
    """PC-stable with the d-separation oracle as the CI test (exact)."""
    import itertools

    n = dag.n
    adj = ~np.eye(n, dtype=bool)
    sepsets: dict[tuple[int, int], tuple[int, ...]] = {}
    ell = 0
    cap = n - 2 if max_level is None else max_level
    while True:
        adj_prev = adj.copy()
        for i in range(n):
            nbrs = [int(v) for v in np.flatnonzero(adj_prev[i])]
            for j in nbrs:
                if not adj[i, j]:
                    continue
                cand = [v for v in nbrs if v != j]
                if len(cand) < ell:
                    continue
                for s in itertools.combinations(cand, ell):
                    if d_separated(dag, i, j, s):
                        adj[i, j] = adj[j, i] = False
                        sepsets[(min(i, j), max(i, j))] = tuple(s)
                        break
        ell += 1
        max_deg = int(adj.sum(axis=1).max()) if adj.any() else 0
        if max_deg - 1 < ell or ell > cap:
            break
    return adj, sepsets
