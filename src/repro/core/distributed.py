"""Multi-device / multi-pod PC-stable: row-sharded cuPC-S via shard_map.

Parallel decomposition (mirrors cuPC's block grid, but across *chips*):
rows of the compacted adjacency are sharded over every mesh axis flattened
together — within a level PC-stable's tests are embarrassingly parallel, so
the only communication is

  1. all_gather of the per-row winner arrays (t_win, removed_slot, s_win)
     after each chunk   — O(n · n′ · ℓ) ints, tiny vs the CI-test FLOPs;
  2. the replicated adjacency commit (edge removals must be symmetric, i.e.
     row i removing (i,j) must kill row j's edge too — the CUDA version
     does this through global-memory writes, we do it through the gather).

Every chunk is two dispatches — a *tests* shard_map (CI sweep → gathered
winner arrays) and a *commit* (apply winners to the chained adj/sep) — so
the host can keep up to ``pipeline_depth`` chunks' tests in flight while
commits trail behind (see :func:`run_level_sharded`). The split is what
makes dispatch-ahead safe: tests only read an *alive snapshot* of the
adjacency, and a snapshot that lags the commits produces extra claims only
on already-removed edges, which the chained commit discards — results are
bit-identical for any depth (tests/test_sharding.py).

With ``engine="S-grid"`` the chunk cadence disappears entirely: the rank
loop runs inside the Pallas grid (kernels/sgrid.py) and each launch is ONE
fused tests+commit shard_map (:func:`_grid_fused_fn`) — the pipelined
deque collapses to a single sharded launch, normally one per level. The
level-end max-degree sync is then the only host round-trip, and
``speculate=True`` hides it by dispatching level ℓ+1's first chunk under
level ℓ's compaction bound while the sync resolves
(:func:`_speculative_dispatch`).

State layout — every combination is bit-identical (tests/test_sharding.py):

  * C replicated (default): every device holds the full (n,n) C. Fine to
    n ≈ 16k (≤ 1 GB fp32), zero extra comms.
  * C row-sharded (``shard_c=True``): C is sharded with the SAME row layout
    as the compacted adjacency (one ``core/sharding.py`` spec for both),
    so each device keeps only its n²/n_dev block. The CI tests of shard
    rows i only read C[a,b] with a ∈ shard ∪ cols, b ∈ cols ∪ {anything
    for local rows}, where cols is the set of still-active candidate ids
    (vertices with degree ≥ 1 — every conditioning-set member and every
    tested j is one). The O(n·k) column block C[:, cols] is all-gathered
    ONCE per level into the :class:`ColumnCache` (and later levels merely
    *subset* the cached block — C is constant and cols only shrink, so no
    further collective is ever needed); per-device C memory is
    O(n·k + n²/n_dev) and the full n×n matrix never exists on one device.
  * sepsets row-sharded (``shard_sep=True``): the (n, n, depth) sepset
    tensor rows are sharded with the same row layout; each chunk's commit
    writes winner sepsets shard-locally (levels.commit_sep_rows) and only
    the O(n²) bool adjacency symmetrization stays replicated. Per-device
    sepset memory drops from O(n²·depth) to O(n²·depth / n_dev) — at
    depth 8 and fp32-width slots that is 32 n² bytes replicated → 32 n² /
    n_dev, the last replicated O(n²·depth) state. The global tensor is
    reassembled only on host at run end (and for checkpoint callbacks).

Fault tolerance: the (adj, sep) pair after any level is a complete,
idempotent checkpoint; the driver snapshots it per level so a restart
replays at most one level.
"""
from __future__ import annotations

import functools
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro import obs

from . import levels as L
from . import sharding as S
from .compact import compact_rows
from .sharding import AXIS


def pc_mesh(devices=None) -> Mesh:
    """1-D mesh over all local devices; the PC row axis."""
    return S.make_mesh(devices=devices)


def shard_correlation(c, mesh: Mesh):
    """Place C row-sharded for ``shard_c`` runs: rows padded to a shard
    multiple with the same layout as the compacted adjacency. Returns the
    (n_pad, n) sharded array; per-device footprint is n_pad·n/n_dev."""
    return S.shard_rows(jnp.asarray(c, jnp.float32), mesh)[0]


def _active_columns(counts_host: np.ndarray, n: int):
    """Host-side candidate-column plan for the sharded-C gather.

    Every id a CI test reads through the gathered columns — conditioning-set
    members AND tested neighbours j — is some row's compacted neighbour,
    i.e. a vertex of degree ≥ 1 (symmetry). cols is that set, padded to a
    bucketed static width k (duplicating cols[0], whose gathered column
    values are identical, so duplicate positions cannot perturb parity) to
    keep the shard_map compile key stable across levels.

    Returns host arrays (cols (k,) int32, col_pos (n,) int32, k).
    """
    cols = np.flatnonzero(counts_host[:n] > 0).astype(np.int32)
    k = max(1, min(L.bucket_npr(len(cols)), n))
    col_pos = np.zeros(n, np.int32)
    col_pos[cols] = np.arange(len(cols), dtype=np.int32)
    if len(cols) < k:
        cols = np.concatenate([cols, np.full(k - len(cols), cols[0], np.int32)])
    return cols[:k], col_pos, k


class ColumnCache:
    """Per-run hot-column cache for the row-sharded C layout.

    The PR-3 path all-gathered C[:, cols] inside EVERY chunk body — the
    same bytes re-shipped ``chunks`` times per level. But C is constant for
    the whole run and the candidate set (degree ≥ 1 vertices) only ever
    shrinks, so one gathered block stays a valid superset forever:

      * level-boundary "invalidation" recomputes cols from the fresh degree
        counts and — when the new set is a subset of the cached one, which
        degree monotonicity guarantees — *subsets* the cached block locally
        (levels.subset_cols): zero collectives after the first level;
      * the first shard_c level (or a resume with no cache) pays the single
        O(n·k) all-gather.

    The cached block is replicated (n_pad, k) fp32; its values are exactly
    what a fresh gather would produce, so parity is untouched
    (tests/test_sharding.py asserts skeleton/sepset equality AND that the
    per-level gather count strictly decreases vs the uncached path).

    ``gathers`` counts collective column gathers performed over the run —
    the benchmark and the cache-regression test read it.
    """

    def __init__(self):
        self.c_cols = None  # (n_pad, k) replicated device block
        self.member = None  # (n,) bool — ids present in the cached cols
        self.col_pos = None  # (n,) int32 — id → position in cached block
        self.gathers = 0

    def level_block(self, c_rows, mesh: Mesh, counts_host: np.ndarray, n: int):
        """The level's (c_cols, col_pos, k, level_gathers) — subsetting the
        cache when possible, all-gathering (and counting it) otherwise."""
        cols, col_pos, k = _active_columns(counts_host, n)
        real = np.flatnonzero(counts_host[:n] > 0)
        level_gathers = 0
        if self.c_cols is not None and bool(np.all(self.member[real])):
            c_cols = L.subset_cols(self.c_cols, jnp.asarray(self.col_pos[cols]))
        else:  # first level (or defensive rebuild): the one collective
            c_cols = _gather_cols_fn(mesh)(
                c_rows, S.replicate(jnp.asarray(cols), mesh)
            )
            self.gathers += 1
            level_gathers = 1
        self.c_cols = c_cols
        self.member = np.zeros(n, bool)
        self.member[real] = True
        self.col_pos = col_pos
        return c_cols, col_pos, k, level_gathers


def _shard_rows_ids(n_l: int):
    """Global row ids of this shard inside a shard_map body."""
    shard_idx = jax.lax.axis_index(AXIS)
    return shard_idx * n_l + jnp.arange(n_l, dtype=jnp.int32)


def _gather_winners(t_win, removed_slot, s_win):
    """Shared epilogue of the tests bodies: all_gather the per-row winner
    arrays to full (n_pad, …) width — O(n·n′·ℓ) ints, the only per-chunk
    cross-shard traffic besides the (cached) column gather."""
    return (
        jax.lax.all_gather(t_win, AXIS, tiled=True),
        jax.lax.all_gather(removed_slot, AXIS, tiled=True),
        jax.lax.all_gather(s_win, AXIS, tiled=True),
    )


@functools.lru_cache(maxsize=64)
def _gather_cols_fn(mesh: Mesh):
    """One-per-level column gather for the ColumnCache: each shard's local
    (n_l, k) slice of C[:, cols] all-gathered to a replicated (n_pad, k)."""

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(P(AXIS), P()), out_specs=P(),
        check_vma=False,
    )
    def _gather(c_rows, cols):
        return jax.lax.all_gather(c_rows[:, cols], AXIS, tiled=True)

    return jax.jit(_gather)


@functools.lru_cache(maxsize=64)
def _tests_fn(mesh: Mesh, ell: int, n_chunk: int, n_max: int):
    """Tests-only shard_map for the replicated-C layout: CI-sweep one chunk
    on this shard's rows and return gathered full-width winner arrays.
    lru_cache'd so bucketed (ℓ, n_chunk, n′) configs reuse the compiled
    program across levels and calls (Mesh is hashable)."""

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(), P(), P(AXIS), P(AXIS), P(), P()),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    def _tests(c, adj, compact_l, counts_l, t0, tau):
        rows_l = _shard_rows_ids(compact_l.shape[0])
        ranks = t0 + jnp.arange(n_chunk, dtype=L._rank_dtype())
        sep_found, s_ids = L._tests_s(
            c, adj, compact_l, counts_l, rows_l, ranks, tau, ell=ell, n_max=n_max
        )
        return _gather_winners(*L._winners(sep_found, ranks, s_ids, None))

    return jax.jit(_tests)


@functools.lru_cache(maxsize=64)
def _tests_sharded_c_fn(mesh: Mesh, ell: int, n_chunk: int, n_max: int, k: int,
                        cached: bool):
    """Tests-only shard_map for the ROW-SHARDED C layout.

    c_rows arrives sharded with the same row spec as the compacted
    adjacency. cached=True receives the level's replicated (n_pad, k)
    hot-column block (ColumnCache) — no collective in the body; cached=False
    is the legacy per-chunk gather, kept for the cache's regression
    benchmark/test. Either way the full n×n matrix never exists per device.
    """
    if cached:

        @functools.partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=(P(AXIS), P(), P(), P(AXIS), P(AXIS), P(), P(), P()),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )
        def _tests(c_rows, c_cols, adj, compact_l, counts_l, col_pos, t0, tau):
            rows_l = _shard_rows_ids(compact_l.shape[0])
            ranks = t0 + jnp.arange(n_chunk, dtype=L._rank_dtype())
            sep_found, s_ids = L._tests_s_cols(
                c_rows, c_cols, col_pos, adj, compact_l, counts_l, rows_l,
                ranks, tau, ell=ell, n_max=n_max,
            )
            return _gather_winners(*L._winners(sep_found, ranks, s_ids, None))

    else:

        @functools.partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=(P(AXIS), P(), P(AXIS), P(AXIS), P(), P(), P(), P()),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )
        def _tests(c_rows, adj, compact_l, counts_l, cols, col_pos, t0, tau):
            rows_l = _shard_rows_ids(compact_l.shape[0])
            ranks = t0 + jnp.arange(n_chunk, dtype=L._rank_dtype())
            # the per-chunk O(n·k) column gather (uncached legacy path)
            c_cols = jax.lax.all_gather(c_rows[:, cols], AXIS, tiled=True)
            sep_found, s_ids = L._tests_s_cols(
                c_rows, c_cols, col_pos, adj, compact_l, counts_l, rows_l,
                ranks, tau, ell=ell, n_max=n_max,
            )
            return _gather_winners(*L._winners(sep_found, ranks, s_ids, None))

    return jax.jit(_tests)


def _grid_commit(adj, sep, compact_full, t_win, rem, s_win, *, ell, shard_sep):
    """Shared commit tail of the grid shard_map bodies: apply gathered
    full-width winner arrays to the chained (adj, sep) — the replicated
    commit, or the shard-local sepset commit when sep is row-sharded.
    Mirrors :func:`_commit_fn`'s body exactly (same tie-break inputs)."""
    n = adj.shape[0]
    rows = jnp.arange(n, dtype=jnp.int32)
    if not shard_sep:
        return L._global_commit(
            adj, sep, compact_full, rows, t_win[:n], rem[:n], s_win[:n], ell
        )
    row_ids = _shard_rows_ids(sep.shape[0])
    _, key_mat = L._commit_key_mat(compact_full, rows, t_win[:n], rem[:n], n)
    sep_new = L.commit_sep_rows(
        sep, row_ids, adj, key_mat, compact_full, rem[:n], s_win[:n], ell
    )
    return L.commit_adj(adj, key_mat), sep_new


@functools.lru_cache(maxsize=64)
def _grid_tests_fn(mesh: Mesh, ell: int, n_chunk: int, n_max: int,
                   shard_c: bool, k: int, cached: bool):
    """Tests-only shard_map for the GRID-RESIDENT engine: one kernel launch
    sweeps every rank of the chunk on this shard's rows (rank axis in the
    Pallas grid — kernels/sgrid.py) and returns gathered full-width winner
    arrays. Used by the speculative dispatch of level ℓ+1's first chunk;
    the normal grid path fuses the commit too (:func:`_grid_fused_fn`)."""
    from repro.kernels.ops import chunk_s_grid_tests, chunk_s_grid_tests_cols

    if shard_c:
        in_specs = (P(AXIS), P(), P(), P(AXIS), P(AXIS), P(), P(), P())

        @functools.partial(jax.shard_map, mesh=mesh, in_specs=in_specs,
                           out_specs=(P(), P(), P()), check_vma=False)
        def _tests(c_rows, c_cols, adj, compact_l, counts_l, col_pos, t0, tau):
            rows_l = _shard_rows_ids(compact_l.shape[0])
            return _gather_winners(*chunk_s_grid_tests_cols(
                c_rows, c_cols, col_pos, adj, compact_l, counts_l, rows_l,
                t0, tau, ell=ell, n_chunk=n_chunk, n_max=n_max,
            ))

    else:

        @functools.partial(jax.shard_map, mesh=mesh,
                           in_specs=(P(), P(), P(AXIS), P(AXIS), P(), P()),
                           out_specs=(P(), P(), P()), check_vma=False)
        def _tests(c, adj, compact_l, counts_l, t0, tau):
            rows_l = _shard_rows_ids(compact_l.shape[0])
            return _gather_winners(*chunk_s_grid_tests(
                c, adj, compact_l, counts_l, rows_l, t0, tau,
                ell=ell, n_chunk=n_chunk, n_max=n_max,
            ))

    return jax.jit(_tests)


@functools.lru_cache(maxsize=64)
def _grid_fused_fn(mesh: Mesh, ell: int, n_chunk: int, n_max: int,
                   shard_sep: bool, shard_c: bool, k: int, cached: bool):
    """The grid engine's whole chunk as ONE dispatch: grid-resident CI sweep
    of every rank on this shard's rows → winner all_gather → commit, fused
    in a single jitted shard_map. With the default launch budget one call
    covers one whole level — the pipelined dispatcher's deque collapses to
    this single sharded launch (host dispatches per level: 1)."""
    from repro.kernels.ops import chunk_s_grid_tests, chunk_s_grid_tests_cols

    sep_spec = P(AXIS) if shard_sep else P()

    if shard_c and cached:
        in_specs = (P(AXIS), P(), P(), sep_spec, P(AXIS), P(AXIS), P(), P(),
                    P(), P())

        @functools.partial(jax.shard_map, mesh=mesh, in_specs=in_specs,
                           out_specs=(P(), sep_spec), check_vma=False)
        def _fused(c_rows, c_cols, adj, sep, compact_l, counts_l, col_pos,
                   compact_full, t0, tau):
            rows_l = _shard_rows_ids(compact_l.shape[0])
            winners = _gather_winners(*chunk_s_grid_tests_cols(
                c_rows, c_cols, col_pos, adj, compact_l, counts_l, rows_l,
                t0, tau, ell=ell, n_chunk=n_chunk, n_max=n_max,
            ))
            return _grid_commit(adj, sep, compact_full, *winners,
                                ell=ell, shard_sep=shard_sep)

    elif shard_c:
        in_specs = (P(AXIS), P(), sep_spec, P(AXIS), P(AXIS), P(), P(), P(),
                    P(), P())

        @functools.partial(jax.shard_map, mesh=mesh, in_specs=in_specs,
                           out_specs=(P(), sep_spec), check_vma=False)
        def _fused(c_rows, adj, sep, compact_l, counts_l, cols, col_pos,
                   compact_full, t0, tau):
            rows_l = _shard_rows_ids(compact_l.shape[0])
            c_cols = jax.lax.all_gather(c_rows[:, cols], AXIS, tiled=True)
            winners = _gather_winners(*chunk_s_grid_tests_cols(
                c_rows, c_cols, col_pos, adj, compact_l, counts_l, rows_l,
                t0, tau, ell=ell, n_chunk=n_chunk, n_max=n_max,
            ))
            return _grid_commit(adj, sep, compact_full, *winners,
                                ell=ell, shard_sep=shard_sep)

    else:
        in_specs = (P(), P(), sep_spec, P(AXIS), P(AXIS), P(), P(), P())

        @functools.partial(jax.shard_map, mesh=mesh, in_specs=in_specs,
                           out_specs=(P(), sep_spec), check_vma=False)
        def _fused(c, adj, sep, compact_l, counts_l, compact_full, t0, tau):
            rows_l = _shard_rows_ids(compact_l.shape[0])
            winners = _gather_winners(*chunk_s_grid_tests(
                c, adj, compact_l, counts_l, rows_l, t0, tau,
                ell=ell, n_chunk=n_chunk, n_max=n_max,
            ))
            return _grid_commit(adj, sep, compact_full, *winners,
                                ell=ell, shard_sep=shard_sep)

    return jax.jit(_fused)


@functools.lru_cache(maxsize=64)
def _commit_fn(mesh: Mesh, ell: int, shard_sep: bool):
    """Commit one chunk's gathered winner arrays to the chained (adj, sep).

    shard_sep=False: the replicated commit (levels._global_commit) — every
    device updates its full (n, n, depth) sepset copy.
    shard_sep=True: sep stays P(AXIS) row-sharded; the body computes the
    replicated adjacency symmetrization (levels.commit_adj — the ONLY
    remaining replicated commit) plus this shard's sepset rows
    (levels.commit_sep_rows). Winner arrays arrive at gathered (n_pad, …)
    width and are sliced to n (shard-pad rows have no claims).
    """
    sep_spec = P(AXIS) if shard_sep else P()

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(), sep_spec, P(), P(), P(), P()),
        out_specs=(P(), sep_spec),
        check_vma=False,
    )
    def _commit(adj, sep, compact_full, t_win, rem, s_win):
        n = adj.shape[0]
        rows = jnp.arange(n, dtype=jnp.int32)
        if not shard_sep:
            return L._global_commit(
                adj, sep, compact_full, rows, t_win[:n], rem[:n], s_win[:n], ell
            )
        row_ids = _shard_rows_ids(sep.shape[0])
        _, key_mat = L._commit_key_mat(compact_full, rows, t_win[:n], rem[:n], n)
        sep_new = L.commit_sep_rows(
            sep, row_ids, adj, key_mat, compact_full, rem[:n], s_win[:n], ell
        )
        return L.commit_adj(adj, key_mat), sep_new

    return jax.jit(_commit)


def run_level_sharded(c, adj, sep, ell, tau, mesh,
                      cell_budget=L.DEFAULT_CELL_BUDGET, bucket=True,
                      shard_c: bool = False, shard_sep: bool = False,
                      pipeline_depth: int = 1, col_cache: ColumnCache | None = None,
                      engine: str = "S", spec: dict | None = None):
    """Distributed analogue of levels.run_level (cuPC-S engine), on the same
    chunk planner: bucketed n′/chunk shapes keep one compiled shard_map
    program live across level boundaries per mesh too.

    shard_c: c is the ROW-SHARDED (n_pad, n) matrix from
    :func:`shard_correlation` instead of a replicated (n, n) one.
    shard_sep: sep is the ROW-SHARDED (n_pad, n, depth) tensor (same
    layout); commits write this shard's rows only.
    pipeline_depth: chunks' tests kept in flight before the oldest commit
    is applied (1 = fully synchronous). Tests dispatched while commits
    trail read an alive snapshot ≤ depth−1 chunks stale — bit-identical
    results for any depth (see levels.chunk_s_tests).
    col_cache: the run's :class:`ColumnCache` (shard_c only); None gathers
    columns inside every chunk body (the pre-cache layout).
    engine: "S" (chunked tests/commit shard_maps, pipelined via the deque)
    or "S-grid" (the grid-resident kernel: every rank of a launch sweeps
    inside ONE fused tests+commit shard_map — the deque collapses to a
    single sharded launch, normally one per level).
    spec: a speculative first chunk from :func:`_speculative_dispatch`
    (grid engine only) — its winner arrays were computed under the
    PREVIOUS level's compaction bound before the max-degree sync resolved;
    consumed here by slicing them to this level's (narrower or equal)
    width, which is exact because slots past a row's degree can never
    hold claims. Stats report ``speculative=True`` on a hit.
    """
    n = adj.shape[0]
    n_dev = S.mesh_size(mesh)
    grid = str(engine).upper() == "S-GRID"
    if grid and cell_budget == L.DEFAULT_CELL_BUDGET:
        cell_budget = L.GRID_CELL_BUDGET  # see levels.GRID_CELL_BUDGET
    counts_host = np.asarray(jax.device_get(jnp.sum(adj, axis=1)))
    npr = int(counts_host.max(initial=0))
    if npr - 1 < ell:
        return adj, sep, {"skipped": True, "chunks": 0, "dispatches": 0,
                          "npr": npr}

    # pad rows to a device multiple; padded rows have counts=0 → fully masked
    pad = S.pad_amount(n, mesh)
    npr_b, n_chunk, total = L.plan_level(
        npr, ell, max((n + pad) // n_dev, 1), engine="S",
        cell_budget=cell_budget, bucket=bucket, n_cols=n,
    )
    compact_host, counts_full = compact_rows(adj, n_prime=npr_b)
    compact_rep = S.replicate(compact_host, mesh)  # the commit's full view
    compact, _ = S.shard_rows(compact_host, mesh, fill=-1)
    counts, _ = S.shard_rows(counts_full, mesh)

    depth = max(1, int(pipeline_depth))
    stats = {"skipped": False, "npr": npr, "npr_bucket": npr_b,
             "n_chunk": n_chunk, "total_sets": total, "shard_c": shard_c,
             "shard_sep": shard_sep, "pipeline_depth": 1 if grid else depth,
             "engine": "S-grid" if grid else "S",
             "compile_key": (ell, n_chunk, npr_b)}
    if shard_c:
        if col_cache is not None:
            c_cols, col_pos, k, gathers = col_cache.level_block(
                c, mesh, counts_host, n
            )
            tests = _tests_sharded_c_fn(mesh, ell, n_chunk, npr_b, k, cached=True)
            # c_cols is already replicated (gather out_specs P(); a subset of
            # a replicated array stays replicated) — no extra device_put
            pre_args = (c, c_cols)
            mid_args = (S.replicate(jnp.asarray(col_pos), mesh),)
            stats["col_gathers"] = gathers
        else:
            cols, col_pos, k = _active_columns(counts_host, n)
            tests = _tests_sharded_c_fn(mesh, ell, n_chunk, npr_b, k, cached=False)
            pre_args = (c,)
            # replicate the column plan once per level, not once per chunk
            mid_args = (S.replicate(jnp.asarray(cols), mesh),
                        S.replicate(jnp.asarray(col_pos), mesh))
        stats["k_cols"] = k
        stats["c_sharding"] = str(c.sharding)
    else:
        k = 0
        tests = _tests_fn(mesh, ell, n_chunk, npr_b)
        pre_args = (c,)
        mid_args = ()

    chunks = 0
    dispatches = 0
    if grid:
        # the grid-resident engine: every launch is ONE fused tests+commit
        # shard_map (the rank loop lives in the kernel grid) — no deque, no
        # split dispatch; normally a single launch covers the whole level
        cached = col_cache is not None
        fused = _grid_fused_fn(mesh, ell, n_chunk, npr_b, shard_sep,
                               shard_c, k, cached)
        commit = _commit_fn(mesh, ell, shard_sep)
        t_next = 0
        if (spec is not None and spec.get("ell") == ell
                and spec["npr_b"] >= npr_b):
            # the speculative first chunk (dispatched under the previous
            # compaction, overlapping the max-degree sync): slice its
            # winner arrays to this level's width and commit — slots past
            # a row's degree are alive-masked, so the slice drops nothing
            t_win, rem, s_win = spec["winners"]
            adj, sep = commit(adj, sep, compact_rep, t_win[:, :npr_b],
                              rem[:, :npr_b], s_win[:, :npr_b])
            chunks += 1
            dispatches += 1  # the commit; the tests ran under the sync
            t_next = spec["n_chunk"]
            stats["speculative"] = True
        for t0 in range(t_next, total, n_chunk):
            adj, sep = fused(
                *pre_args, adj, sep, compact, counts, *mid_args, compact_rep,
                jnp.asarray(t0, L._rank_dtype()), jnp.float32(tau),
            )
            chunks += 1
            dispatches += 1
    else:
        commit = _commit_fn(mesh, ell, shard_sep)
        pending: deque = deque()
        for t0 in range(0, total, n_chunk):
            pending.append(tests(
                *pre_args, adj, compact, counts, *mid_args,
                jnp.asarray(t0, L._rank_dtype()), jnp.float32(tau),
            ))
            chunks += 1
            if len(pending) >= depth:
                adj, sep = commit(adj, sep, compact_rep, *pending.popleft())
        while pending:
            adj, sep = commit(adj, sep, compact_rep, *pending.popleft())
        dispatches = 2 * chunks  # one tests + one commit program per chunk

    stats["chunks"] = chunks
    stats["dispatches"] = dispatches
    if shard_sep:
        # the sepset blocks the commit shard_map wrote
        stats["sep_row_blocks"] = _row_blocks(sep)
    if shard_c:
        stats["c_row_blocks"] = _row_blocks(c)  # the C this level read
        if col_cache is None:
            stats["col_gathers"] = chunks  # one collective per chunk body
        # bytes the column collective(s) shipped this level (fp32)
        stats["col_gather_bytes"] = stats["col_gathers"] * (n + pad) * k * 4
    obs.record_level_stats(stats, level=ell, layout="sharded")
    return adj, sep, stats


def _row_blocks(a) -> list:
    """(device id, first row, end row) of each of a's addressable shards —
    layout metadata only, no device sync."""
    return sorted((s.device.id, *s.index[0].indices(a.shape[0])[:2])
                  for s in a.addressable_shards)


def _speculative_dispatch(c, adj, ell, tau, mesh, prev_npr_b, n,
                          shard_c, col_cache, cell_budget, bucket):
    """Dispatch level ``ell``'s first grid chunk BEFORE the max-degree host
    sync resolves, using the PREVIOUS level's compaction bound as the width
    guess (degrees only shrink, so it always bounds the fresh width).

    Everything here is host-async: the device-side re-compaction
    (compact_rows is pure jnp), the shard placement, and the grid tests
    shard_map are all enqueued without reading a device value — so the
    subsequent ``device_get(max_deg)`` level barrier overlaps useful work
    instead of idling the mesh. ``run_level_sharded`` consumes the result
    when the level actually runs (slicing the winner arrays to the fresh
    width — exact, see its docstring) or drops it when the run stops.

    With ``shard_c`` the tests read the run's cached hot-column block
    (whose values equal any fresh gather — C is constant and the candidate
    set only shrinks); an unpopulated cache (or cache_cols=False) skips
    speculation. Returns the spec dict or None.
    """
    n_dev = S.mesh_size(mesh)
    pad = S.pad_amount(n, mesh)
    if cell_budget == L.DEFAULT_CELL_BUDGET:
        cell_budget = L.GRID_CELL_BUDGET  # mirror run_level_sharded's upgrade
    try:
        npr_b, n_chunk, _ = L.plan_level(
            prev_npr_b, ell, max((n + pad) // n_dev, 1), engine="S",
            cell_budget=cell_budget, bucket=bucket, n_cols=n,
        )
    except ValueError:  # rank capacity — let the real level raise (or stop)
        return None
    compact_full, counts_full = compact_rows(adj, n_prime=npr_b)
    compact_sh, _ = S.shard_rows(compact_full, mesh, fill=-1)
    counts_sh, _ = S.shard_rows(counts_full, mesh)
    t0 = jnp.asarray(0, L._rank_dtype())
    tau = jnp.float32(tau)
    if shard_c:
        if col_cache is None or col_cache.c_cols is None:
            return None
        k = int(col_cache.c_cols.shape[1])
        tests = _grid_tests_fn(mesh, ell, n_chunk, npr_b, True, k, True)
        winners = tests(c, col_cache.c_cols, adj, compact_sh, counts_sh,
                        S.replicate(jnp.asarray(col_cache.col_pos), mesh),
                        t0, tau)
    else:
        tests = _grid_tests_fn(mesh, ell, n_chunk, npr_b, False, 0, False)
        winners = tests(c, adj, compact_sh, counts_sh, t0, tau)
    return {"ell": ell, "npr_b": npr_b, "n_chunk": n_chunk, "winners": winners}


def pc_distributed(
    x=None,
    c=None,
    m: int | None = None,
    alpha: float = 0.01,
    mesh: Mesh | None = None,
    max_level: int | None = None,
    sepset_depth: int = 8,
    cell_budget: int = L.DEFAULT_CELL_BUDGET,
    checkpoint_cb=None,
    resume=None,
    bucket: bool = True,
    shard_c: bool = False,
    shard_sep: bool = False,
    cache_cols: bool = True,
    pipeline_depth: int = 1,
    engine: str = "S",
    speculate: bool = False,
):
    """Distributed PC-stable. Provide samples x (m,n) or corr matrix c + m.

    Memory/latency knobs — every combination is bit-identical (skeleton,
    sepsets, CPDAG) to the replicated path and the single-device "S"
    engine, including n % n_dev ≠ 0 (tests/test_sharding.py):

    shard_c=True row-shards the correlation matrix over the mesh (same
    layout as the compacted adjacency) — per-device C memory drops from
    O(n²) to O(n·k + n²/n_dev).
    shard_sep=True row-shards the (n, n, sepset_depth) sepset tensor with
    the same layout and commits winner rows shard-locally — per-device
    sepset memory drops from O(n²·depth) to O(n²·depth / n_dev); the
    O(n²) bool adjacency symmetrization is the sole replicated commit.
    cache_cols (shard_c only): gather the active-column block once per
    level into a :class:`ColumnCache` and subset it thereafter, instead of
    re-gathering C[:, cols] in every chunk body (False = legacy traffic).
    pipeline_depth ≥ 2 keeps that many chunks' tests in flight per level —
    chunk t+1's gather/unrank overlaps chunk t's commit (double-buffered
    dispatch at depth 2); the level barrier is the only host sync.
    engine="S-grid" runs every level's rank sweep grid-resident
    (kernels/sgrid.py): one fused tests+commit shard_map per launch —
    normally ONE host dispatch per level — instead of the chunked deque
    (pipeline_depth is then moot and ignored).
    speculate=True (grid engine only) dispatches level ℓ+1's first chunk
    under level ℓ's compaction bound BEFORE the max-degree sync resolves,
    so the one remaining host round-trip per level overlaps device work
    (:func:`_speculative_dispatch`) — bit-identical results either way.

    checkpoint_cb(level, adj, sep): optional per-level snapshot hook — the
    fault-tolerance unit for multi-pod runs (levels are idempotent). With
    shard_sep the callback receives the n-row global VIEW of the sharded
    tensor (a lazy jax.Array slice — np.asarray / jax.device_get in the
    callback assembles it on host), so snapshots are layout-agnostic and
    feed straight back into ``resume=``.
    resume=(level, adj, sep): restart from a per-level snapshot — the
    whole algorithm state is (adjacency, sepsets, level); replaying a
    level is safe (PC-stable levels are deterministic given G').
    """
    from .cit import correlation_from_samples, threshold
    from .combinadics import MAX_LEVEL
    from .orient import cpdag_from_skeleton
    from .pc import PCRun

    tracer = obs.run_tracer("pc_distributed")
    with tracer.span("total", engine=str(engine), shard_c=shard_c,
                     shard_sep=shard_sep, pipeline_depth=pipeline_depth,
                     speculate=speculate):
        mesh = mesh or pc_mesh()
        if c is None:
            assert x is not None
            m = int(x.shape[0])
            c = correlation_from_samples(jnp.asarray(x, jnp.float32))
        c = jnp.asarray(c, jnp.float32)
        n = c.shape[0]
        lmax = min(max_level if max_level is not None else MAX_LEVEL,
                   sepset_depth)

        if resume is not None:
            start_level, adj0, sep0 = resume
            adj = jnp.asarray(adj0)
            sep = jnp.asarray(sep0, jnp.int32)
            first_level = start_level + 1
        else:
            adj = L.level0(c, threshold(m, 0, alpha))
            sep = jnp.full((n, n, sepset_depth), -1, jnp.int32)
            sep = sep.at[:, :, 0].set(jnp.where(adj, -1, -2))
            first_level = 1

        if shard_c:
            # one placement for the whole run: the padded row blocks live on
            # their shard from here on (level 0 above still used the host copy)
            c = shard_correlation(c, mesh)
        if shard_sep:
            # same row layout as C/compacted adjacency: (n_pad, n, depth)
            sep = S.shard_rows(sep, mesh, fill=-1)[0]
        col_cache = ColumnCache() if (shard_c and cache_cols) else None

        grid = str(engine).upper() == "S-GRID"
        if str(engine).upper() not in ("S", "S-GRID"):
            raise ValueError(
                f"pc_distributed engine must be 'S' or 'S-grid', got {engine!r}"
            )
        if speculate and not grid:
            raise ValueError("speculate=True requires engine='S-grid'")

        stats = []
        ell = first_level
        spec = None
        prev_npr_b = None
        while ell <= lmax:
            if speculate and prev_npr_b is not None:
                # overlap the level barrier: level ℓ's first grid chunk goes
                # out under level ℓ-1's compaction bound before max_deg
                # resolves
                spec = _speculative_dispatch(
                    c, adj, ell, threshold(m, ell, alpha), mesh, prev_npr_b,
                    n, shard_c, col_cache, cell_budget, bucket,
                )
            max_deg = int(jax.device_get(jnp.max(jnp.sum(adj, axis=1))))
            if max_deg - 1 < ell:
                break  # a pending spec chunk is dropped (never committed)
            with tracer.span(f"level{ell}", level=ell) as sp:
                adj, sep, st = run_level_sharded(
                    c, adj, sep, ell, threshold(m, ell, alpha),
                    mesh, cell_budget=cell_budget,
                    bucket=bucket, shard_c=shard_c,
                    shard_sep=shard_sep,
                    pipeline_depth=pipeline_depth,
                    col_cache=col_cache,
                    engine=engine, spec=spec)
                spec = None
                sp.sync(adj, sep).set(**{k: st[k] for k in
                                         ("engine", "chunks", "dispatches",
                                          "total_sets", "npr_bucket",
                                          "col_gathers", "speculative")
                                         if k in st})
            stats.append({"level": ell, **st})
            prev_npr_b = st.get("npr_bucket") if not st.get("skipped") else None
            if checkpoint_cb is not None:
                checkpoint_cb(ell, adj, sep[:n] if shard_sep else sep)
            ell += 1

        if shard_sep:
            sep = sep[:n]  # drop shard padding before orientation/export
        max_deg = int(jax.device_get(jnp.max(jnp.sum(adj, axis=1))))
        cpdag = cpdag_from_skeleton(adj, sep, n_prime=min(n, L.bucket_npr(max(max_deg, 1))))
        run = PCRun(
            adj=np.asarray(jax.device_get(adj)),
            cpdag=np.asarray(jax.device_get(cpdag)),
            sepsets=np.asarray(jax.device_get(sep)),
            levels_run=ell - 1,
            level_stats=stats,
        )
    run.timings_s = tracer.timings()
    tracer.finish(driver="pc_distributed", engine=str(engine), n=n,
                  n_dev=S.mesh_size(mesh), levels_run=run.levels_run)
    return run
