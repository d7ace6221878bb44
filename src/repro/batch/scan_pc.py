"""Fixed-shape, fully-traced PC-stable — the compile-once formulation.

``core/pc.pc_from_corr`` is a *host* loop: every level syncs the max degree
back to Python, plans chunk shapes, and dispatches jitted chunk functions.
That is the right shape for one huge graph, but for many-graph workloads
(bootstrap replicates, alpha sweeps, per-module datasets) the per-run host
traffic dominates. ``pc_scan`` re-states the whole skeleton phase as ONE
traced program with static shapes:

  * the level loop is unrolled at trace time over ``ell = 1..max_level``
    (the static level cap — paper runs stop at single digits);
  * each level ℓ is a masked dense sweep over all ``C(w_ell, ell)``
    combo-ranks of a width-``w_ell`` compacted adjacency, processed in a
    ``lax.fori_loop`` over rank chunks (budget-bounded, no host sync);
  * the CI math and the commit are *the same traced functions* the "S"
    engine uses (``levels._tests_s`` / ``levels._commit``), so every
    accept/reject decision and every sepset winner is bit-identical to
    ``pc_from_corr(engine="S")`` up to the level cap (asserted by
    tests/test_batch.py).

Why chunk boundaries don't matter for parity: the per-edge sepset winner is
the whole-level lexicographic minimum of (rank, endpoint-order) — ranks
ascend across chunks, so any chunking (including "one chunk = everything")
commits the same winner (see core/levels.py docstring).

Width schedules. The host driver re-plans its worklist width from the live
max degree at every level; a traced program cannot. A single conservative
width (the level-0 degree bound) is always exact but sweeps
``C(w, ell)`` ranks at every level — quadratically wasteful once degrees
shrink. ``n_prime`` therefore also accepts a per-level tuple
``(w_1, …, w_max_level)``; ``plan_schedule`` discovers a tight schedule for
a whole batch by probing level-by-level (ONE host sync per level for all B
graphs — versus B syncs per level for the sequential loop). Exactness is
*checked inside the trace*: each graph's ``ok`` output is True iff every
level's width bounded that graph's live max degree (or the level was a
provable no-op), i.e. the result is bit-identical to the unconstrained run.
Rows wider than the schedule are degree-capped deterministically (their
neighbour list is truncated at compaction), never silently corrupted —
re-run flagged graphs with ``n_prime=None`` to get exact results.

``pc_scan_batch`` wraps the same core in ``jax.vmap`` + ``jax.jit``: one
XLA program learns B graphs per dispatch. ``scan_levels_batch`` is the
plan-as-you-go variant (one sync per level, schedule discovered on the
fly) used by the bootstrap ensemble.

Alpha sweeps. The Fisher-z thresholds enter the trace as a DATA vector
(one tau per level), not as compile-time constants: one compiled program
serves every (m, alpha) combination of a given shape, and the batch entry
points accept per-graph tau vectors. ``alpha_sweep`` exploits this for the
ParallelPC-style workload — B significance levels over ONE correlation
matrix, broadcast (not recomputed) across the batch lanes of a single
dispatch. The serving layer (repro/serve) admits such sweeps through the
same slot policy as ordinary requests.

Multi-device: both batch entry points accept ``mesh`` (a flat 1-D mesh
from ``core/sharding.py``). The leading B axis is then sharded over the
mesh via ``jax.sharding`` — the SAME compiled program runs on every
device over its B/n_dev local graphs (XLA partitions the vmapped program
along the batch dim; there is no cross-graph communication in the
skeleton phase, so the only collective is the per-level max-degree
reduction in ``scan_levels_batch`` — still ONE host sync per level for
the whole sharded batch). A batch not divisible by the device count is
padded with identity-correlation graphs (empty level-0 skeletons — a few
masked no-op lanes) and trimmed from every output; results are
bit-identical to the single-device run (tests/test_sharding.py).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import levels as L
from repro.core.cit import threshold
from repro.core.compact import compact_rows
from repro.core.levels import DEFAULT_CELL_BUDGET
from repro.core.orient import cpdag_from_skeleton

#: Default static level cap for the traced path. PC on bounded-degree graphs
#: rarely needs more; deeper runs should pass max_level explicitly (each
#: additional level adds a statically unrolled masked sweep to the program).
DEFAULT_MAX_LEVEL = 3


class ScanResult(NamedTuple):
    """Pytree result of the traced PC run (leading batch axis when vmapped).

    adj:     (..., n, n) bool   skeleton
    cpdag:   (..., n, n) bool   CPDAG digraph (== adj when orient=False)
    sepsets: (..., n, n, Lmax) int32, -1 padded, -2 sentinel in slot 0 for
             level-0 removals — same convention as core/pc.PCRun.
    ok:      (...,) bool        PER-GRAPH exactness certificate: True iff
             the static width schedule bounded this graph's live max degree
             at every level (result is exact); False marks a degree-capped
             (approximate) run.
    max_degs: (..., max_level) int32 — live max degree at each level's
             start; max_degs[ℓ-1] - 1 < ℓ means the host driver would have
             stopped before level ℓ (lets callers report true levels-run).
    ok_levels: (..., max_level) bool — the per-LEVEL factorisation of
             ``ok`` (``ok == ok_levels.all(-1)``): which level's width was
             the one that capped the graph. Levels run through the dense
             ℓ=1 cube are exact at any degree and always report True.

    Retry contract (the serving layer's escalation policy relies on it):
    an ``ok=False`` graph was NOT silently corrupted — rows wider than the
    schedule had their sorted neighbour lists deterministically truncated
    at compaction — and re-running THE SAME graph with a width schedule
    that satisfies every level (e.g. the next-wider bucket per failing
    ``ok_levels`` entry, or ``n_prime=None`` for the per-graph exact
    level-0 bound) yields a run with ``ok=True`` whose adj/sepsets/cpdag
    are bit-identical to the unconstrained single-graph ``pc_scan``.
    Escalating the width can therefore be repeated until ``ok`` flips,
    and the first ``ok=True`` result is THE exact answer — there is
    nothing to reconcile across attempts.
    """

    adj: jax.Array
    cpdag: jax.Array
    sepsets: jax.Array
    ok: jax.Array
    max_degs: jax.Array
    ok_levels: jax.Array


# --------------------------------------------------------------------------
# static planning
# --------------------------------------------------------------------------
def plan_n_prime(cs, m: int, alpha: float = 0.01, tau0=None) -> int:
    """Single static compact width valid for a whole batch of correlation
    matrices: the bucketed level-0 max degree over every graph.

    Levels only remove edges, so this bounds every row at every level —
    always exact (``ok`` True), but conservative; ``plan_schedule`` finds
    the tight per-level widths. One fused device pass + one host sync.

    ``tau0`` optionally overrides the level-0 threshold derived from
    (m, alpha): a scalar, or a (B,) vector of per-graph thresholds (the
    per-graph tau path of :func:`pc_scan_batch` / :func:`alpha_sweep`).
    """
    cs = jnp.asarray(cs, jnp.float32)
    if cs.ndim == 2:
        cs = cs[None]
    if tau0 is None:
        tau0 = threshold(m, 0, alpha)
    tau0 = jnp.broadcast_to(jnp.asarray(tau0, jnp.float32), (cs.shape[0],))
    deg = jax.vmap(lambda c, t: jnp.max(jnp.sum(L.level0(c, t), axis=1)))(cs, tau0)
    npr = int(obs.fetch(jnp.max(deg), site="scan.plan_n_prime"))
    n = int(cs.shape[-1])
    return max(1, min(L.bucket_npr(npr), n))


def _plan_chunk(n: int, w: int, ell: int, cell_budget: int, m: int = 0):
    """Static (n_chunk, steps) for one level's rank sweep — same budget math
    as levels.plan_level's S-engine branch, with power-of-two chunk lengths
    so the fori_loop body shape recurs across levels. When the whole sweep
    fits one chunk there is nothing to reuse — take the exact length.

    ``m > 0`` switches to the discrete G² cost model: the dominant tensor is
    the (m, n·n_chunk·w) joint-code table, so per-rank cells scale with the
    sample count rather than the ℓ² Gaussian gather footprint (mirrors the
    budget rescale in engines.run_level's discrete branch)."""
    total = math.comb(w, ell)
    if total == 0:
        return 0, 0
    if m > 0:
        per_rank_cells = n * w * m
    else:
        per_rank_cells = n * w * max(ell, 1) * max(ell, 1)
    budget_chunk = max(1, cell_budget // max(per_rank_cells, 1))
    if budget_chunk >= total:
        return total, 1
    n_chunk = max(1, min(L._pow2_ceil(total), L._pow2_floor(budget_chunk)))
    steps = -(-total // n_chunk)
    return n_chunk, steps


def _use_dense_l1(n: int, w: int, cell_budget: int) -> bool:
    """Static choice for level 1: the closed-form dense (i, j, k) cube beats
    the compacted sweep when compaction saves little (w near n) and the n³
    cube fits the dispatch budget — the budget the caller already divided
    by B, so the vmapped cube respects the same per-dispatch memory ceiling
    as every other path. Dense is also exact at ANY degree (no width
    truncation), so it never trips the ok flag."""
    return w * 2 >= n and n ** 3 <= cell_budget


def _level1_dense(c, adj, sep, tau):
    """Level 1 as one fused elementwise pass over the dense (i, j, k) cube.

    Exactly the arithmetic ``levels._tests_s`` performs at ℓ=1 — where
    M2 = C[k,k] = 1 so the "inverse" is exact and every term collapses to
    the closed form ρ(i,j|k) = (C_ij − C_ik·C_jk)/√((1−C_ik²)(1−C_jk²)) —
    followed by the same deterministic winner commit the Pallas L1-dense
    engine uses (``levels.commit_dense_l1``; bit-identical to chunk_s per
    its docstring and tests/test_engines.py). No unranking, no gathers, no
    masked-rank waste: the paper's "ℓ=1 dominates" level as n³ flops.
    """
    from repro.core.cit import fisher_z

    n = c.shape[0]
    cik = c[:, None, :]  # C[i,k] broadcast over j
    cjk = c[None, :, :]  # C[j,k] broadcast over i
    g = 1.0 / jnp.maximum(jnp.ones((), c.dtype), 1e-8)  # M2 = C[k,k] = 1
    u_i = g * cik
    var_i = 1.0 - cik * u_i
    num = c[:, :, None] - cjk * u_i
    var_j = 1.0 - cjk * (g * cjk)
    rho = num / jnp.sqrt(jnp.maximum(var_i * var_j, 1e-20))
    indep = fisher_z(rho) <= tau

    ks = jnp.arange(n, dtype=jnp.int32)
    mask = adj[:, None, :] & adj[:, :, None] & (ks[None, None, :] != ks[None, :, None])
    sep_found = indep & mask  # (i, j, k)
    big = jnp.int32(2**30)
    kwin = jnp.min(jnp.where(sep_found, ks[None, None, :], big), axis=-1)
    return L.commit_dense_l1(adj, sep, kwin)


def _as_schedule(n_prime, max_level: int, n: int) -> tuple:
    """Normalise int-or-tuple n_prime to a max_level-long width tuple."""
    if isinstance(n_prime, (tuple, list)):
        ws = [int(w) for w in n_prime]
        if len(ws) < max_level:
            ws += [ws[-1] if ws else n] * (max_level - len(ws))
        ws = ws[:max_level]
    else:
        ws = [int(n_prime)] * max_level
    return tuple(max(1, min(w, n)) for w in ws)


# --------------------------------------------------------------------------
# traced level sweep (shared by the one-program scan and the level driver)
# --------------------------------------------------------------------------
def _level_sweep(c, adj, sep, tau, *, ell: int, w: int, n_chunk: int, steps: int,
                 jitter: float = L.DEFAULT_JITTER):
    """One level's masked dense rank sweep at static width w.

    Rows with more than w neighbours are degree-capped: compaction truncates
    their (sorted) neighbour list and counts are clamped to w, so every test
    is well-formed — the caller's ok flag records whether capping could have
    happened at all. ``jitter`` feeds the per-set SPD inverse (escalated by
    the serving layer's degradation ladder; default = every engine's
    baseline).
    """
    n = c.shape[0]
    rd = L._rank_dtype()
    rows = jnp.arange(n, dtype=jnp.int32)
    compact, counts = compact_rows(adj, n_prime=w)
    counts = jnp.minimum(counts, w)

    def body(step, carry):
        adj, sep = carry
        ranks = jnp.asarray(step, rd) * n_chunk + jnp.arange(n_chunk, dtype=rd)
        sep_found, s_ids = L._tests_s(
            c, adj, compact, counts, rows, ranks, tau, ell=ell, n_max=w,
            jitter=jitter,
        )
        return L._commit(
            c, adj, sep, compact, counts, sep_found, ranks, s_ids, None, ell
        )

    if steps == 1:
        return body(0, (adj, sep))
    return jax.lax.fori_loop(0, steps, body, (adj, sep))


def _level_sweep_g2(stats, adj, sep, alpha, *, ell: int, w: int, n_chunk: int,
                    steps: int, r: int):
    """Discrete twin of :func:`_level_sweep`: the same masked rank sweep at
    static width w, with the G² worklist (``levels.chunk_g2``) as the chunk
    body. ``alpha`` is the traced per-level scalar (the decision happens in
    p-value space per cell); ``r`` is the static run-wide max arity."""
    rd = L._rank_dtype()
    compact, counts = compact_rows(adj, n_prime=w)
    counts = jnp.minimum(counts, w)

    def body(step, carry):
        adj, sep = carry
        t0 = jnp.asarray(step, rd) * n_chunk
        return L.chunk_g2(
            stats, adj, sep, compact, counts, t0, alpha,
            ell=ell, n_chunk=n_chunk, n_max=w, r=r,
        )

    if steps == 1:
        return body(0, (adj, sep))
    return jax.lax.fori_loop(0, steps, body, (adj, sep))


def _level_ok(max_deg, ell: int, w: int):
    """Exactness certificate for one level at static width w: the width
    bounded the live max degree, OR no row had enough neighbours for any
    CI test at this level (max_deg ≤ ell ⇒ the level is a no-op — the only
    candidate conditioning set of a full row contains the target)."""
    return (max_deg <= w) | (max_deg <= ell)


# --------------------------------------------------------------------------
# one-program scan
# --------------------------------------------------------------------------
def _scan_core(
    c,
    taus,
    *,
    schedule: tuple,
    sepset_depth: int,
    cell_budget: int,
    orient: bool,
    jitter: float,
    test=None,
) -> ScanResult:
    """One graph's full skeleton phase as a single traced computation.

    ``taus`` is a TRACED (max_level+1,) fp32 vector of per-level decision
    scalars — data, not a compile-time constant — so one compiled
    program serves every (m, alpha) of a given shape, and the vmapped
    caller can carry a different threshold vector per batch lane (the
    alpha-sweep workload). For the Gaussian test the entries are Fisher-z
    thresholds; for a discrete ``test`` (a STATIC DiscreteCITest riding the
    build cache key) they are α per level, ``c`` carries DiscreteStats, and
    each level runs the G² worklist sweep (no dense-ℓ1 shortcut — that cube
    is partial-correlation arithmetic).
    """
    discrete = test is not None and test.kind == "discrete"
    if discrete:
        n = c.codes.shape[1]
        adj = L.level0_g2(c, taus[0], r=test.r)
    else:
        n = c.shape[0]
        adj = L.level0(c, taus[0])
    sep = jnp.full((n, n, sepset_depth), -1, jnp.int32)
    sep = sep.at[:, :, 0].set(jnp.where(adj, -1, -2))

    max_degs, ok_levels = [], []
    for ell, w in enumerate(schedule, start=1):
        max_deg = jnp.max(jnp.sum(adj, axis=1)).astype(jnp.int32)
        max_degs.append(max_deg)
        if not discrete and ell == 1 and _use_dense_l1(n, w, cell_budget):
            # exact at any degree — no width truncation, no ok contribution
            ok_levels.append(jnp.asarray(True))
            adj, sep = _level1_dense(c, adj, sep, taus[1])
            continue
        ok_levels.append(_level_ok(max_deg, ell, w))
        n_chunk, steps = _plan_chunk(n, w, ell, cell_budget,
                                     m=int(test.m) if discrete else 0)
        if steps == 0:
            continue  # C(w, ell) == 0: statically no work (ok still checked)
        if discrete:
            adj, sep = _level_sweep_g2(
                c, adj, sep, taus[ell], ell=ell, w=w, n_chunk=n_chunk,
                steps=steps, r=test.r,
            )
            continue
        adj, sep = _level_sweep(
            c, adj, sep, taus[ell], ell=ell, w=w, n_chunk=n_chunk, steps=steps,
            jitter=jitter,
        )

    cpdag = cpdag_from_skeleton(adj, sep) if orient else adj
    max_degs = jnp.stack(max_degs) if max_degs else jnp.zeros((0,), jnp.int32)
    ok_levels = (jnp.stack(ok_levels) if ok_levels
                 else jnp.ones((0,), bool))
    return ScanResult(adj=adj, cpdag=cpdag, sepsets=sep,
                      ok=jnp.all(ok_levels), max_degs=max_degs,
                      ok_levels=ok_levels)


@functools.lru_cache(maxsize=None)
def _build(schedule, sepset_depth, cell_budget, orient, jitter, batched,
           test=None):
    core = functools.partial(
        _scan_core,
        schedule=schedule,
        sepset_depth=sepset_depth,
        cell_budget=cell_budget,
        orient=orient,
        jitter=jitter,
        test=test,
    )
    return jax.jit(jax.vmap(core) if batched else core)


def _pad_shard_batch(cs, taus, mesh):
    """Pad the batch to a device-count multiple with identity-correlation
    graphs (level 0 removes every edge → all levels are masked no-ops for
    the pad lanes; their tau vector is an arbitrary positive constant) and
    place both batch-sharded. Returns (cs, taus, pad)."""
    from repro.core import sharding as SH

    pad = SH.pad_amount(cs.shape[0], mesh)
    if pad:
        n = cs.shape[-1]
        eye = jnp.broadcast_to(jnp.eye(n, dtype=cs.dtype), (pad, n, n))
        cs = jnp.concatenate([cs, eye], axis=0)
        taus = jnp.concatenate(
            [taus, jnp.ones((pad, taus.shape[-1]), taus.dtype)], axis=0
        )
    # already a multiple: no 0-fill
    return SH.shard_batch(cs, mesh)[0], SH.shard_batch(taus, mesh)[0], pad


def _trim_result(res: ScanResult, pad: int) -> ScanResult:
    """Drop the identity-graph pad lanes from every (B, ...) output."""
    from repro.core.sharding import unpad_leading

    if pad == 0:
        return res
    return ScanResult(*(unpad_leading(a, pad) for a in res))


def taus_for(m: int, alpha: float, max_level: int) -> tuple:
    """Per-level Fisher-z threshold vector for one (m, alpha): the host-side
    companion of the traced tau input (tuple of max_level+1 floats)."""
    return tuple(threshold(m, ell, alpha) for ell in range(max_level + 1))


def _prep(c, m, alpha, max_level, sepset_depth, n_prime, taus=None, test=None):
    discrete = test is not None and getattr(test, "kind", "gaussian") == "discrete"
    if discrete:
        n = int(c.codes.shape[-1])
    else:
        c = jnp.asarray(c, jnp.float32)
        n = int(c.shape[-1])
    if max_level is None:
        max_level = DEFAULT_MAX_LEVEL
    if max_level > sepset_depth:
        raise ValueError(
            f"max_level={max_level} exceeds sepset_depth={sepset_depth}: "
            "sepsets of the deepest level would not fit"
        )
    if taus is None:
        taus = (test.taus(max_level) if discrete
                else taus_for(m, alpha, max_level))
    taus = jnp.asarray(taus, jnp.float32)
    if taus.shape[-1] != max_level + 1:
        raise ValueError(
            f"taus must carry max_level+1={max_level + 1} per-level "
            f"thresholds; got shape {taus.shape}"
        )
    if n_prime is None:
        if discrete:
            test.check_level(max_level)
            adj0 = L.level0_g2(c, float(taus[0]), r=test.r)
            npr = int(obs.fetch(jnp.max(jnp.sum(adj0, axis=1)), site="scan.prep"))
            n_prime = max(1, min(L.bucket_npr(npr), n))
        else:
            n_prime = plan_n_prime(c, m, alpha, tau0=taus[..., 0])
    schedule = _as_schedule(n_prime, max_level, n)
    return c, taus, max_level, schedule


def pc_scan(
    c,
    m: int,
    alpha: float = 0.01,
    max_level: int | None = None,
    sepset_depth: int = 8,
    n_prime=None,
    cell_budget: int = DEFAULT_CELL_BUDGET,
    orient: bool = True,
    taus=None,
    jitter: float = L.DEFAULT_JITTER,
    test=None,
) -> ScanResult:
    """Traced PC-stable on one correlation matrix c (n, n).

    Bit-identical skeleton/sepsets to ``pc_from_corr(engine="S",
    max_level=max_level)`` whenever the returned ``ok`` is True — which is
    guaranteed for the default ``n_prime=None`` (plans the exact level-0
    degree bound from ``c``, one host sync). ``n_prime`` may be an int
    (one width for every level) or a per-level tuple from
    ``plan_schedule``. ``max_level=None`` uses DEFAULT_MAX_LEVEL.

    ``taus`` overrides the (m, alpha)-derived per-level thresholds with an
    explicit (max_level+1,) vector — thresholds are trace DATA, so varying
    them reuses the compiled program. ``jitter`` escalates the Tikhonov
    regularisation of the ℓ≥2 SPD inverses (the serving layer's
    degradation ladder; the default is every engine's baseline and keeps
    results bit-identical to engine="S").

    ``test``: a discrete :class:`~repro.core.cit.DiscreteCITest` switches
    the program to the G² sweep — ``c`` must then be the test's
    DiscreteStats pytree (``DiscreteCITest.from_samples``); taus carry α
    per level. None/Gaussian keeps the bit-identical Fisher-z path.
    """
    if test is not None and getattr(test, "kind", "gaussian") != "discrete":
        test = None  # Gaussian rides the default path — one build cache line
    c, taus, max_level, schedule = _prep(
        c, m, alpha, max_level, sepset_depth, n_prime, taus, test=test
    )
    fn = _build(schedule, sepset_depth, int(cell_budget), bool(orient),
                float(jitter), False, test)
    return fn(c, taus)


def pc_scan_batch(
    cs,
    m: int,
    alpha: float = 0.01,
    max_level: int | None = None,
    sepset_depth: int = 8,
    n_prime=None,
    cell_budget: int = DEFAULT_CELL_BUDGET,
    orient: bool = True,
    mesh=None,
    taus=None,
    jitter: float = L.DEFAULT_JITTER,
    test=None,
) -> ScanResult:
    """Vmapped ``pc_scan`` over a leading batch axis: cs (B, n, n).

    One XLA program per (B, n, static-args) processes all B graphs per
    dispatch — no per-graph host loop. Pass ``n_prime=plan_schedule(...)``
    for throughput (tight per-level widths; per-graph ``ok`` certifies
    exactness), or leave ``None`` for the always-exact level-0 bound. The
    per-dispatch cell budget is divided by B so the batched worklists keep
    the same memory ceiling as the single-graph engines.

    ``taus``: per-graph per-level threshold vectors, shape (B, max_level+1)
    (or (max_level+1,) broadcast to every lane) — lanes may carry DIFFERENT
    (m, alpha) combinations in one dispatch since thresholds are trace
    data. This is what lets :func:`alpha_sweep` and the serving layer's
    admission policy co-batch requests that share only (n, schedule).

    mesh (core/sharding.py): shard the batch axis over the mesh — each
    device runs the same program on its B/n_dev local graphs, the budget
    divides by the LOCAL batch (per-device memory is what it bounds), and
    a non-divisible B is padded with identity graphs and trimmed. Results
    are bit-identical to mesh=None (chunking never affects the committed
    winners — see core/levels.py).
    """
    if test is not None and getattr(test, "kind", "gaussian") == "discrete":
        raise NotImplementedError(
            "pc_scan_batch is Gaussian-only for now: batching the discrete "
            "G² sweep needs a per-lane DiscreteStats layout — run graphs "
            "through pc_scan(test=...) individually"
        )
    cs = jnp.asarray(cs, jnp.float32)
    if cs.ndim != 3:
        raise ValueError(f"pc_scan_batch expects (B, n, n); got shape {cs.shape}")
    b = int(cs.shape[0])
    with obs.span("pc_scan_batch", batch=b, n=int(cs.shape[1]),
                  sharded=mesh is not None) as sp:
        cs, taus, max_level, schedule = _prep(
            cs, m, alpha, max_level, sepset_depth, n_prime, taus
        )
        taus = jnp.broadcast_to(taus, (b, max_level + 1))
        pad = 0
        if mesh is not None:
            from repro.core import sharding as SH

            cs, taus, pad = _pad_shard_batch(cs, taus, mesh)
            b_local = (b + pad) // SH.mesh_size(mesh)
        else:
            b_local = b
        budget = max(int(cell_budget) // max(b_local, 1), 2**16)
        fn = _build(schedule, sepset_depth, budget, bool(orient),
                    float(jitter), True)
        res = _trim_result(fn(cs, taus), pad)
        sp.set(schedule=list(schedule)).sync(res.adj)
    return res


def alpha_sweep(
    c,
    m: int,
    alphas,
    max_level: int | None = None,
    sepset_depth: int = 8,
    n_prime=None,
    cell_budget: int = DEFAULT_CELL_BUDGET,
    orient: bool = True,
    mesh=None,
    jitter: float = L.DEFAULT_JITTER,
) -> ScanResult:
    """Significance-level sweep over ONE correlation matrix: lane k of the
    returned batch is ``pc_scan(c, m, alpha=alphas[k])``, bit-identically
    (tested) — but C is computed once and broadcast across the lanes of a
    single vmapped dispatch instead of rebuilt per alpha, and the whole
    sweep shares one compiled program (thresholds are trace data).

    The default ``n_prime=None`` plans the level-0 degree bound at
    ``max(alphas)``: the loosest test keeps a SUPERSET of every other
    lane's level-0 edges, and levels only remove edges, so that single
    width bounds every lane at every level — the sweep is exact
    (``ok`` all True) with one planning sync. This is the ParallelPC
    workload (PAPERS.md, arXiv 1510.03042) as pure admission policy.
    """
    c = jnp.asarray(c, jnp.float32)
    if c.ndim != 2:
        raise ValueError(f"alpha_sweep expects one (n, n) matrix; got {c.shape}")
    alphas = [float(a) for a in alphas]
    if not alphas:
        raise ValueError("alpha_sweep needs at least one alpha")
    lmax = DEFAULT_MAX_LEVEL if max_level is None else max_level
    taus = jnp.asarray([taus_for(m, a, lmax) for a in alphas], jnp.float32)
    if n_prime is None:
        n_prime = plan_n_prime(c, m, alpha=max(alphas))
    cs = jnp.broadcast_to(c, (len(alphas),) + c.shape)
    return pc_scan_batch(
        cs, m, max_level=lmax, sepset_depth=sepset_depth, n_prime=n_prime,
        cell_budget=cell_budget, orient=orient, mesh=mesh, taus=taus,
        jitter=jitter,
    )


# --------------------------------------------------------------------------
# level-synced batch driver + schedule planning
# --------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _build_dense_l1():
    return jax.jit(jax.vmap(_level1_dense, in_axes=(0, 0, 0, 0)))


@functools.lru_cache(maxsize=None)
def _build_orient():
    return jax.jit(jax.vmap(cpdag_from_skeleton))


@functools.lru_cache(maxsize=None)
def _build_level(ell, w, n_chunk, steps):
    """Jitted vmapped one-level sweep, cached on its static shape key so the
    same compiled program serves every level/batch with that shape. The
    per-graph tau is a batched input (alpha may differ across lanes)."""

    def step(c, adj, sep, tau):
        return _level_sweep(c, adj, sep, tau, ell=ell, w=w, n_chunk=n_chunk, steps=steps)

    return jax.jit(jax.vmap(step, in_axes=(0, 0, 0, 0)))


@functools.partial(jax.jit, static_argnames=("depth",))
def _batch_init(cs, tau0, depth):
    """Vmapped level 0 + sepset-tensor init for a whole batch (tau0: (B,))."""
    adj = jax.vmap(L.level0)(cs, tau0)
    b, n = cs.shape[0], cs.shape[-1]
    sep = jnp.full((b, n, n, depth), -1, jnp.int32)
    sep = sep.at[..., 0].set(jnp.where(adj, -1, -2))
    return adj, sep


def scan_levels_batch(
    cs,
    m: int,
    alpha: float = 0.01,
    max_level: int | None = None,
    sepset_depth: int = 8,
    cell_budget: int = DEFAULT_CELL_BUDGET,
    orient: bool = True,
    bucket: bool = True,
    mesh=None,
    taus=None,
):
    """Batch PC with per-level re-planning: ONE host sync per level for all
    B graphs (the sequential loop pays B syncs per level).

    Discovers the tight width schedule on the fly — each level's static
    width is the (bucketed) live max degree across the whole batch, so
    every result is exact (``ok`` all True) and the jitted per-level
    programs recur across calls via their (ell, w, n_chunk, steps) cache
    key. ``bucket=False`` uses exact max-degree widths instead — fewer
    masked cells per sweep at the cost of one compile per exact degree;
    right for recurring workloads whose shapes repeat (same tradeoff as
    ``levels.run_level(bucket=...)``). Returns ``(ScanResult, schedule)``;
    feed the schedule to ``pc_scan_batch`` to run the same workload as one
    fused program with zero level syncs.

    ``taus``: per-graph (B, max_level+1) threshold vectors like
    :func:`pc_scan_batch` — lanes with different (m, alpha) probe ONE
    shared width per level (the batch max), so mixed-alpha slots and
    alpha sweeps plan exactly like uniform batches.

    mesh (core/sharding.py): shard the batch axis — the per-level width
    probe stays ONE host sync per level for the whole sharded batch (the
    max-degree reduction becomes the only cross-device collective).
    """
    cs = jnp.asarray(cs, jnp.float32)
    if cs.ndim != 3:
        raise ValueError(f"scan_levels_batch expects (B, n, n); got {cs.shape}")
    b, n = int(cs.shape[0]), int(cs.shape[-1])
    if max_level is None:
        max_level = DEFAULT_MAX_LEVEL
    if max_level > sepset_depth:
        raise ValueError(f"max_level={max_level} exceeds sepset_depth={sepset_depth}")
    if taus is None:
        taus = taus_for(m, alpha, max_level)
    taus = jnp.broadcast_to(jnp.asarray(taus, jnp.float32), (b, max_level + 1))
    pad = 0
    b_local = b
    if mesh is not None:
        from repro.core import sharding as SH

        cs, taus, pad = _pad_shard_batch(cs, taus, mesh)
        b_local = (b + pad) // SH.mesh_size(mesh)
    budget = max(int(cell_budget) // max(b_local, 1), 2**16)

    adj, sep = _batch_init(cs, taus[:, 0], sepset_depth)

    schedule, max_degs = [], []
    for ell in range(1, max_level + 1):
        deg_b = jnp.max(jnp.sum(adj, axis=-1), axis=-1).astype(jnp.int32)  # (B,)
        max_degs.append(deg_b)
        max_deg = int(jax.device_get(jnp.max(deg_b)))
        w = max(1, min(L.bucket_npr(max_deg) if bucket else max_deg, n))
        schedule.append(w)
        if max_deg - 1 < ell:
            continue  # no graph can run this level; keep probing widths
        if ell == 1 and _use_dense_l1(n, w, budget):
            adj, sep = _build_dense_l1()(cs, adj, sep, taus[:, 1])
            continue
        n_chunk, steps = _plan_chunk(n, w, ell, budget)
        if steps == 0:
            continue
        fn = _build_level(ell, w, n_chunk, steps)
        adj, sep = fn(cs, adj, sep, taus[:, ell])

    cpdag = _build_orient()(adj, sep) if orient else adj
    ok = jnp.ones((b + pad,), bool)  # widths track the live bound by construction
    ok_levels = jnp.ones((b + pad, len(schedule)), bool)
    max_degs = (jnp.stack(max_degs, axis=-1) if max_degs
                else jnp.zeros((b + pad, 0), jnp.int32))
    res = _trim_result(
        ScanResult(adj=adj, cpdag=cpdag, sepsets=sep, ok=ok, max_degs=max_degs,
                   ok_levels=ok_levels),
        pad,
    )
    return res, tuple(schedule)


def plan_schedule(
    cs,
    m: int,
    alpha: float = 0.01,
    max_level: int | None = None,
    sepset_depth: int = 8,
    cell_budget: int = DEFAULT_CELL_BUDGET,
    bucket: bool = True,
    mesh=None,
    taus=None,
) -> tuple:
    """Tight per-level width schedule for a batched workload.

    Runs the level-synced driver once (≈ one steady-state batch run) and
    returns its discovered widths. Use for recurring workloads: plan on a
    pilot batch, then serve every later batch through the one-program
    ``pc_scan_batch`` and re-run the rare ``ok=False`` stragglers with
    ``n_prime=None``. ``bucket=False`` plans exact max-degree widths
    (fewest masked cells; one compile per exact degree). ``mesh`` shards
    the planning pass's batch axis like :func:`scan_levels_batch`;
    ``taus`` plans under per-graph thresholds (mixed-alpha slots).
    """
    _, schedule = scan_levels_batch(
        cs, m, alpha=alpha, max_level=max_level, sepset_depth=sepset_depth,
        cell_budget=cell_budget, orient=False, bucket=bucket, mesh=mesh,
        taus=taus,
    )
    return schedule
