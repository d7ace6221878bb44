"""Plain PC-stable for Gaussian data: the benchmark's reference.

Straight numpy in float64, written from the PC-stable definition (Colombo
& Maathuis 2014; cuPC, arXiv:1812.08491, Algorithm 1), importing nothing
of the program under test. Its n^3 level-1 tests are screened in float32
in one jnp pass on the default device, and every pair the screen puts
near tau is recomputed in float64 (``screen_margin`` bounds the screen's
error).

What it computes:

* level 0 removes i - j when the Fisher z of c_ij is <= tau_0;
* level l >= 1 tests, for every edge alive when the level starts, every
  l-subset S of adj(i) minus j and of adj(j) minus i in the starting graph,
  and removes the edge when some test gives z(i, j | S) <= tau_l, with
  z = |atanh(rho)| and tau_l = Phi^-1(1 - alpha/2) / sqrt(m - l - 3);
* the levels stop when no row has more than l neighbours, or at max_level.

Which separating set is recorded is a convention. This one is cuPC's,
made deterministic: within row i, sets are ranked in lexicographic order
of their positions in i's sorted neighbour list (j included, sets that
hold j skipped); row i's claim on the edge is key = 2 * rank + (i > j);
the edge takes the set of the smaller of its two endpoint claims.

Two entry points:

* :func:`pc_stable` runs the algorithm from the samples' correlation and
  returns (skeleton, sepsets) in the program's array layout;
* :func:`check` takes a program's (skeleton, sepsets) and measures how far
  each decision the output implies lies on the wrong side of tau, with
  the reference's statistic. A correct float32 program misses only by
  rounding near tau; a wrong decision misses by the distance of an
  ordinary test from tau.
"""
from __future__ import annotations

import functools
import itertools
import math
from concurrent.futures import ThreadPoolExecutor
from statistics import NormalDist

import jax
import jax.numpy as jnp
import numpy as np

#: (sets x neighbours) cells a row's block of tests may hold at once
BLOCK_CELLS = 1 << 22
#: sepset slot 0 of an edge removed at level 0, as the program writes it
LEVEL0 = -2


def correlation(x) -> np.ndarray:
    """Sample correlation of x (m, n), in float64."""
    x = np.asarray(x, np.float64)
    xc = x - x.mean(axis=0)
    xn = xc / np.sqrt((xc * xc).sum(axis=0))
    c = xn.T @ xn
    np.fill_diagonal(c, 1.0)
    return c


def threshold(m: int, ell: int, alpha: float) -> float:
    """Fisher-z threshold tau_l for m samples at level l."""
    return NormalDist().inv_cdf(1.0 - alpha / 2.0) / math.sqrt(m - ell - 3)


def z_of(rho):
    """Fisher z = |atanh(rho)|, with |rho| held below 1."""
    r = np.abs(np.asarray(rho))
    return np.arctanh(np.minimum(r, 1 - np.finfo(r.dtype).epsneg))


def _combos(d: int, ell: int) -> np.ndarray:
    """All l-subsets of range(d), lexicographic order, (C(d, l), l)."""
    if ell == 1:
        return np.arange(d, dtype=np.int64)[:, None]
    return np.array(list(itertools.combinations(range(d), ell)),
                    dtype=np.int64).reshape(-1, ell)


def row_rho_blocks(c, i: int, nbrs: np.ndarray, ell: int):
    """Yield (first rank, |rho| block (K_b, d)) for row i at level l >= 1:
    |rho(i, j | S)| for every l-subset S of i's neighbour list (by lex
    rank) and every neighbour j (column), +inf where j is in S."""
    d = nbrs.size
    combos = _combos(d, ell)
    step = max(1, BLOCK_CELLS // max(d, 1))
    cij = c[i, nbrs]
    cjj = c[nbrs, nbrs]
    for r0 in range(0, combos.shape[0], step):
        pos = combos[r0:r0 + step]
        s = nbrs[pos]  # (K, l) variable ids
        b = c[s[:, :, None], nbrs]  # (K, l, d): c[S, j]
        a = c[i, s]  # (K, l): c[i, S]
        if ell == 1:
            minv = 1.0 / c[s, s]  # (K, 1)
            u = a * minv
            h01 = cij[None, :] - u[:, 0:1] * b[:, 0, :]
            h00 = c[i, i] - (u * a)[:, 0]
            h11 = cjj[None, :] - b[:, 0, :] ** 2 * minv
        else:
            minv = np.linalg.inv(c[s[:, :, None], s[:, None, :]])
            u = np.einsum("kl,klm->km", a, minv)
            h01 = cij[None, :] - np.einsum("kl,kld->kd", u, b)
            h00 = c[i, i] - np.einsum("kl,kl->k", u, a)
            h11 = cjj[None, :] - np.einsum("kld,kld->kd", b,
                                           np.einsum("klm,kmd->kld", minv, b))
        rho = np.abs(h01) / np.sqrt(np.maximum(h00[:, None] * h11, np.finfo(h11.dtype).tiny))
        in_s = np.zeros(rho.shape, bool)
        rows = np.arange(pos.shape[0])[:, None]
        in_s[rows, pos] = True
        rho[in_s] = np.inf
        yield r0, rho


def _map_rows(fn, rows, threads):
    if threads <= 1:
        return [fn(i) for i in rows]
    with ThreadPoolExecutor(threads) as ex:
        return list(ex.map(fn, rows))


def _first_sep(c, adj, ell, tau, threads):
    """From-scratch level l: per row, the first separating rank and set
    for every neighbour. Returns {i: (nbrs, rank (d,), sets (d, l))}."""
    rho_tau = math.tanh(tau)

    def row(i):
        nbrs = np.flatnonzero(adj[i])
        d = nbrs.size
        rank = np.full(d, -1, np.int64)
        if d - 1 < ell:
            return i, nbrs, rank
        for r0, rho in row_rho_blocks(c, i, nbrs, ell):
            hit = rho <= rho_tau
            first = np.where(hit.any(axis=0), hit.argmax(axis=0) + r0, -1)
            new = (rank < 0) & (first >= 0)
            rank[new] = first[new]
            if (rank >= 0).all():
                break
        return i, nbrs, rank

    return _map_rows(row, range(adj.shape[0]), threads)


def pc_stable(c, m: int, alpha: float, max_level: int | None = None,
              sepset_depth: int = 8, threads: int = 1, screen: bool = False):
    """PC-stable from a correlation matrix c (n, n), computed in c's dtype.
    Returns (adj (n, n) bool, sepsets (n, n, sepset_depth) int32) in the
    program's layout: -1 padding, -2 in slot 0 for level-0 removals and
    the diagonal. ``screen`` runs level 1 as one float32 device pass
    (:func:`level1_screen`) instead of row by row on the host."""
    c = np.asarray(c)
    n = c.shape[0]
    big = np.iinfo(np.int64).max
    lmax = sepset_depth if max_level is None else min(max_level, sepset_depth)
    z0 = z_of(c)
    adj = (z0 > threshold(m, 0, alpha)) & ~np.eye(n, dtype=bool)
    sep = np.full((n, n, sepset_depth), -1, np.int32)
    sep[:, :, 0] = np.where(adj, -1, LEVEL0)
    ell = 1
    while ell <= lmax and adj.sum(axis=1).max(initial=0) - 1 >= ell:
        tau = threshold(m, ell, alpha)
        key = np.full((n, n), big, np.int64)
        sets = np.zeros((n, n, ell), np.int32)
        if ell == 1 and screen:
            dd = np.sqrt(np.maximum(1 - c * c, 0))
            _, first = level1_screen(c, dd, adj, np.full((n, n), n), math.tanh(tau))
            first = np.asarray(first)
            got = adj & (first < n)
            prefix = np.cumsum(adj, axis=1) - adj  # rank of each id in its row
            rank = np.take_along_axis(prefix, np.minimum(first, n - 1), axis=1)
            bit = np.arange(n)[:, None] > np.arange(n)[None, :]
            key = np.where(got, 2 * rank + bit, big)
            sets[:, :, 0] = first
        else:
            for i, nbrs, rank in _first_sep(c, adj, ell, tau, threads):
                got = rank >= 0
                if not got.any():
                    continue
                j = nbrs[got]
                key[i, j] = 2 * rank[got] + (i > j)
                sets[i, j] = nbrs[_combos(nbrs.size, ell)[rank[got]]]
        removed = (np.minimum(key, key.T) < big) & adj
        chosen = np.where((key <= key.T)[..., None], sets, np.swapaxes(sets, 0, 1))
        ii, jj = np.nonzero(removed)
        sep[ii, jj, :ell] = chosen[ii, jj]
        adj = adj & ~removed
        ell += 1
    return adj, sep


def removal_level(adj, sep) -> np.ndarray:
    """Level at which an output says each edge went: -1 kept, 0 for the
    level-0 sentinel, else the number of ids in its sepset."""
    lvl = np.where(sep[:, :, 0] == LEVEL0, 0, (sep >= 0).sum(axis=2))
    return np.where(adj, -1, lvl)


def _binom_table(n: int, ell: int) -> np.ndarray:
    """C(a, b) for a <= n, b <= l, int64, clipped where it would overflow."""
    cap = np.iinfo(np.int64).max // 4
    return np.array([[min(math.comb(a, b), cap) for b in range(ell + 1)]
                     for a in range(n + 1)], np.int64)


def check(c, m: int, alpha: float, adj, sep, max_level: int | None = None,
          threads: int = 1) -> dict:
    """Hold a program's (adj, sepsets) to the reference statistic.

    A decision the output implies is wrong by a margin, in Fisher-z units,
    when it lies on the wrong side of tau_l: an edge kept past level l that
    some l-set separates, a recorded set that does not separate, or a set
    that separates but comes before the recorded one in the convention's
    order. Returns ``z_gap``, the widest such margin (0.0 when every
    decision agrees), and ``bad``: the count of outputs that no statistic
    can excuse: an asymmetric skeleton or sepset, a set outside the
    starting neighbours, a removal at a level the loop never reached, a
    kept edge with a set.
    """
    c = np.asarray(c, np.float64)
    adj = np.asarray(adj, bool)
    sep = np.asarray(sep)
    n = c.shape[0]
    depth = sep.shape[2]
    lmax = depth if max_level is None else min(max_level, depth)
    off = ~np.eye(n, dtype=bool)
    lvl = removal_level(adj, sep)
    sorted_sep = np.sort(np.where(sep >= 0, sep, n), axis=2)
    bad = int((adj != adj.T).sum() + (lvl != lvl.T).sum()
              + (off & (sorted_sep != np.swapaxes(sorted_sep, 0, 1)).any(axis=2)).sum()
              + (adj & (sep != -1).any(axis=2)).sum())
    tau0 = threshold(m, 0, alpha)
    z0 = z_of(c)
    worst = [float(np.where(lvl == 0, z0 - tau0, tau0 - z0)[off].max(initial=0.0))]
    ell = 1
    while True:
        g = off & ((lvl == -1) | (lvl >= ell))
        if ell > lmax or g.sum(axis=1).max(initial=0) - 1 < ell:
            bad += int((off & (lvl >= ell)).sum())
            break
        bad += _check_level(c, g, lvl, sep, ell, threshold(m, ell, alpha),
                            threads, worst)
        ell += 1
    return {"z_gap": max(worst), "bad": bad}


def _program_keys(g, lvl, sep, ell):
    """The program's claim key on each edge it removed at level l, from
    whichever endpoint's starting list holds its set, and the set's rank in
    each such row. Returns (key (n, n), rank (n, n), count of edges whose
    set fits neither endpoint)."""
    n = g.shape[0]
    big = np.iinfo(np.int64).max
    deg = g.sum(axis=1)
    binom = _binom_table(int(deg.max(initial=0)), ell)
    prefix = np.cumsum(g, axis=1) - g  # position of each id in its row
    ii, jj = np.nonzero(np.triu(g & (lvl == ell), 1))
    s = np.sort(sep[ii, jj, :ell].astype(np.int64), axis=1)
    okset = (s >= 0).all(axis=1) & (np.diff(s, axis=1) > 0).all(axis=1)
    s = np.clip(s, 0, n - 1)
    key = np.full((n, n), big, np.int64)
    rank_at = np.full((n, n), -1, np.int64)
    best = np.full(ii.shape, big, np.int64)
    for a, b in ((ii, jj), (jj, ii)):
        valid = okset & g[a[:, None], s].all(axis=1) & (s != b[:, None]).all(axis=1)
        pos = prefix[a[:, None], s]
        d = deg[a]
        rank = binom[d, ell] - 1
        for t in range(ell):
            rank = rank - binom[np.clip(d - 1 - pos[:, t], 0, None), ell - t]
        rank_at[a[valid], b[valid]] = rank[valid]
        best = np.where(valid, np.minimum(best, 2 * rank + (a > b)), best)
    key[ii, jj] = key[jj, ii] = best
    return key, rank_at, int((best == big).sum())


def _check_level(c, g, lvl, sep, ell, tau, threads, worst) -> int:
    """One level of :func:`check` on the level's starting graph g: appends
    the level's widest violation to ``worst`` and returns the count of sets
    that fit neither endpoint."""
    n = c.shape[0]
    big = np.iinfo(np.int64).max
    key, rank_at, bad = _program_keys(g, lvl, sep, ell)
    i_ = np.arange(n)[:, None]
    bit = (i_ > np.arange(n)[None, :]).astype(np.int64)
    here = g & (lvl == ell)
    # ranks row i claims dependent for j: all of them for an edge kept past
    # the level, those before the program's key for an edge removed at it
    before = np.where(here, np.where(key < big, (key - bit + 1) // 2, 0), big)
    winner = np.where(here & (rank_at >= 0) & (2 * rank_at + bit == key),
                      rank_at, -1)
    if ell == 1:
        worst.append(_check_level1(c, g, before, winner, tau))
        return bad

    def row(i):
        nb = np.flatnonzero(g[i])
        if nb.size - 1 < ell:
            return -np.inf
        out = -np.inf
        for r0, rho in row_rho_blocks(c, i, nb, ell):
            ranks = np.arange(r0, r0 + rho.shape[0])[:, None]
            early = np.where(ranks < before[i, nb][None, :], rho, np.inf)
            if np.isfinite(early).any():
                out = max(out, tau - float(z_of(early.min())))
            w = (winner[i, nb] >= r0) & (winner[i, nb] < r0 + rho.shape[0])
            if w.any():
                zr = z_of(rho[winner[i, nb][w] - r0, np.flatnonzero(w)])
                out = max(out, float((zr - tau).max()))
        return out

    worst.append(max(_map_rows(row, range(n), threads)))
    return bad


def screen_margin(dd) -> float:
    """Fisher-z margin within which a float32 screen of level 1 is redone
    in float64: ten times a bound on the screen's error. Rounding c and dd
    to float32 and the four float32 operations err by under 4e-7 on the
    numerator and relatively on the denominator dd_ik dd_kj, so |rho| errs
    by under 4e-7 / min(dd)^2 (plus 4e-7 |rho|), and z near tau (|rho| <
    0.4) by about as much."""
    off = dd[~np.eye(dd.shape[0], dtype=bool)]
    return 10 * 4e-7 * (1.0 / float(off.min(initial=1.0)) ** 2 + 1.0)


def _rho1_exact(c, dd, i, j, k):
    """float64 |rho(i, j | k)| for index arrays of equal shape."""
    return np.abs(c[i, j] - c[i, k] * c[k, j]) / (dd[i, k] * dd[k, j])


def _check_level1(c, g, before, winner, tau) -> float:
    """Level 1 of the check: all n^3 tests screened in float32 by
    :func:`level1_screen`, every pair the screen puts within
    screen_margin of a wrong decision recomputed in float64."""
    n = c.shape[0]
    dd = np.sqrt(np.maximum(1.0 - c * c, 0.0))
    nbr = _neighbour_ids(g)
    deg = g.sum(axis=1)
    # row i may claim k dependent for j while k's rank is below before[i, j]
    idx = np.minimum(before, np.maximum(deg[:, None] - 1, 0))
    cut = np.where(before < deg[:, None],
                   np.take_along_axis(nbr, idx, axis=1), n)
    min_rho, _ = level1_screen(c, dd, g, cut, 0.0)
    z32 = z_of(np.asarray(min_rho, np.float64))
    worst = -np.inf
    ii, jj = np.nonzero(g & (tau - z32 > -screen_margin(dd)))
    ks = np.arange(n)[None, :]
    step = max(1, BLOCK_CELLS // n)
    for s in range(0, ii.size, step):
        i, j = ii[s:s + step], jj[s:s + step]
        # c and dd are symmetric, so row j holds c[k, j] over k
        with np.errstate(divide="ignore", invalid="ignore"):
            rho = np.abs(c[i, j][:, None] - c[i] * c[j]) / (dd[i] * dd[j])
        ok = g[i] & (ks != j[:, None]) & (ks < cut[i, j][:, None])
        rho = np.where(ok, rho, np.inf).min(axis=1)
        worst = max(worst, float((tau - z_of(rho)).max(initial=-np.inf)))
    wi, wj = np.nonzero(winner >= 0)
    k = nbr[wi, winner[wi, wj]]
    return max(worst, float((z_of(_rho1_exact(c, dd, wi, wj, k)) - tau).max(initial=-np.inf)))


def _neighbour_ids(g):
    """(n, max degree) sorted neighbour ids per row, padded with n."""
    n = g.shape[0]
    width = max(int(g.sum(axis=1).max(initial=0)), 1)
    return np.sort(np.where(g, np.arange(n)[None, :], n), axis=1)[:, :width]


#: rows of the (rows, n, n) level-1 cube one step of the screen holds
SCREEN_ROWS = 16


def level1_screen(c, dd, g, cut, rho_tau: float):
    """Every level-1 test of a starting graph g in one float32 device pass.

    For each ordered pair (i, j) of g, over the conditioning variables k
    in adj(i) minus j with k < cut[i, j]: the least |rho(i, j | k)| (inf if
    no such k) and the first k (by id, n if none) with |rho| <= rho_tau.
    rho(i, j | k) = (c_ij - c_ik c_kj) / (dd_ik dd_kj), dd = sqrt(1 - c^2).
    """
    n = c.shape[0]
    pad = -n % SCREEN_ROWS
    c32 = jnp.asarray(c, jnp.float32)
    dd32 = jnp.asarray(dd, jnp.float32)
    gp = jnp.asarray(np.pad(g, ((0, pad), (0, 0))))
    cutp = jnp.asarray(np.pad(cut, ((0, pad), (0, 0))), jnp.int32)
    rows = jnp.arange(n + pad).reshape(-1, SCREEN_ROWS)
    return _screen(c32, dd32, gp, cutp, rows, jnp.float32(rho_tau), n=n)


@functools.partial(jax.jit, static_argnames=("n",))
def _screen(c, dd, g, cut, rows, rho_tau, *, n):
    ks = jnp.arange(n)

    def block(r):
        ri = jnp.minimum(r, n - 1)
        ci, di = c[ri], dd[ri]  # (B, n): c[i, k], dd[i, k]
        rho = jnp.abs(ci[:, None, :] - ci[:, :, None] * c[None, :, :]) / (
            di[:, :, None] * dd[None, :, :])  # (B, k, j)
        ok = (g[r][:, :, None] & (ks[None, :, None] != ks[None, None, :])
              & (ks[None, :, None] < cut[r][:, None, :]))
        rho = jnp.where(ok, rho, jnp.inf)
        first = jnp.min(jnp.where(rho <= rho_tau, ks[None, :, None], n), axis=1)
        return jnp.min(rho, axis=1), first

    lo, first = jax.lax.map(block, rows)
    return lo.reshape(-1, n)[:n], first.reshape(-1, n)[:n]


def verify(config: dict, traffic: dict, x, output, threads: int = 1) -> dict:
    """The numbers a run's ``correct`` compares for one dataset: x is the
    float32 sample matrix the program was given, output its PCRun."""
    c = correlation(np.asarray(x, np.float64))
    res = check(c, x.shape[0], config["alpha"], output.adj, output.sepsets,
                max_level=traffic["max_level"], threads=threads)
    numbers = {"z_gap": res["z_gap"], "bad": res["bad"]}
    if traffic["orient"]:
        from bench.reference.orient import cpdag

        numbers["cpdag_diff"] = int((cpdag(output.adj, output.sepsets)
                                     != output.cpdag).sum())
    return numbers
