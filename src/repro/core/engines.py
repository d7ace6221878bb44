"""Engine registry: which code path runs a PC-stable level (the paper's
cuPC-E/cuPC-S choice, extended with the Pallas kernel-backed paths).

Names (case-insensitive; ``pc()`` / ``pc_from_corr()`` accept a name or a
``callable(ell) -> name`` for custom per-level hybrids):

  "S"         cuPC-S as jnp/XLA einsums (core/levels.chunk_s) — the
              correctness anchor; fastest pure-XLA path on any backend.
  "E"         cuPC-E as jnp/XLA einsums (core/levels.chunk_e) — paper
              fidelity engine, no pseudo-inverse sharing.
  "S-kernel"  cuPC-S with the per-set Cholesky inverse + CI sweep fused in
              the Pallas kernels (kernels/ops.chunk_s_kernel → cholinv +
              cisweep); gathers stay in XLA. Any level ℓ ≥ 1; at ℓ ≥ 2 as
              degree-class worklists (levels.run_level_classes): each row
              launches only its own rank range and neighbour slots. A
              custom ``chunk_fn_s`` replaces its ℓ = 1 chunk function only.
  "S-grid"    grid-resident cuPC-S (kernels/ops.chunk_s_grid → sgrid): the
              combo-rank loop is a sequential axis of the Pallas grid, the
              winner arrays accumulate in the revisited VMEM output blocks
              and the commit is fused into the same jitted launch — ONE
              host dispatch per level (levels.plan_level_grid statics) on
              every tracked workload, vs ceil(total/n_chunk) for the
              chunked engines. Any level ℓ ≥ 1; bit-identical winners to
              "S" (asserted by tests/test_engines.py).
  "L1-dense"  the fused dense ℓ=1 cube kernel (kernels/ops.level1_dense)
              plus levels.commit_dense_l1 — erases the level that is
              49–83 % of runtime (paper Fig. 6). ℓ=1 only; resolves to
              "S" at ℓ ≥ 2 when requested for a whole run.
  "auto"      the production hybrid: L1-dense at ℓ=1, S-kernel at ℓ≥2.
              Off-TPU the kernels execute in Pallas interpret mode
              (bit-identical decisions, Python speed) — pick "S" for CPU
              throughput, "auto" for hardware runs.
  "G2"        discrete G²/χ² contingency-table test as the jnp worklist
              engine (core/levels.chunk_g2 over the gsq.py XLA reference)
              — requires a discrete CITest (core/cit.DiscreteCITest);
              "S"/"E"/"auto" requested under a discrete test remap here
              (or to "G2-kernel") so callers keep one engine vocabulary.
  "G2-kernel" the same worklist with the per-(edge, sepset) histogram +
              log-term reduction fused in the Pallas kernel
              (kernels/gsq.py; interpret mode off-TPU) — bitwise-identical
              statistics to "G2" (tests/test_kernels.py).
  "scan"      the fixed-shape fully-traced path (repro/batch/scan_pc.py):
              the whole skeleton phase is ONE compiled program up to a
              static level cap — no host loop, vmap-able over a batch of
              graphs. A whole-run engine: pc_from_corr dispatches it before
              the per-level loop; resolve() rejects it at level granularity.

Sharded routes (core/sharding.py owns the mesh/spec/padding conventions):
the row-sharded distributed engine (core/distributed.py, optionally with a
row-sharded C via ``shard_c``) scales ONE graph past a device, and
``batch_run`` below shards the leading B axis of the "scan" engine so a
many-graph workload scales past a device — both through the same flat
1-D mesh and exercised on forced-host CPU devices in CI.

All engines share the chunk planner (levels.plan_level): n′ buckets and
power-of-two chunk lengths keep the jit cache warm across level
boundaries, and one VMEM-aware cell budget bounds every engine's per-
dispatch worklist. All engines commit through the same deterministic
(rank, endpoint-order) winner rule, so skeleton AND sepsets are identical
across engines (asserted by tests/test_engines.py).
"""
from __future__ import annotations

import functools

import jax.numpy as jnp

from repro import obs

from . import levels as L
from .levels import DEFAULT_CELL_BUDGET  # noqa: F401  (re-export; derivation there)

ENGINE_NAMES = ("S", "E", "S-kernel", "S-grid", "L1-dense", "auto", "scan",
                "G2", "G2-kernel")
#: Engines that take over the ENTIRE run (level loop included) instead of a
#: single level; pc_from_corr dispatches them before its level loop.
WHOLE_RUN_ENGINES = ("scan",)
#: Engines of the discrete G² test object (levels.chunk_g2 over contingency
#: tables; "G2-kernel" runs the histogram+reduction in kernels/gsq.py).
DISCRETE_ENGINES = ("G2", "G2-kernel")
_CANON = {name.lower(): name for name in ENGINE_NAMES}


def is_whole_run(engine) -> bool:
    """True when the engine name replaces pc_from_corr's host level loop
    wholesale (currently only "scan", the traced batch path)."""
    return not callable(engine) and str(engine).lower() in (
        n.lower() for n in WHOLE_RUN_ENGINES
    )


def resolve(engine, ell: int, test=None) -> str:
    """Concrete engine for level ℓ. Accepts a name or callable(ell)->name.

    ``test`` (a core/cit.CITest, default Gaussian) gates the (engine ×
    test) matrix: a discrete test remaps the generic names onto its own
    worklist engines ("S"/"E" → "G2", the kernel/auto paths →
    "G2-kernel") and rejects layouts that only exist for correlation
    inputs; requesting "G2*" under a Gaussian test is equally an error.
    """
    if callable(engine):
        engine = engine(ell)
    try:
        name = _CANON[str(engine).lower()]
    except KeyError:
        raise ValueError(f"unknown engine {engine!r}; expected one of {ENGINE_NAMES}")
    if name in WHOLE_RUN_ENGINES:
        raise ValueError(
            f"{name!r} is a whole-run engine (repro/batch/scan_pc.py); it is "
            "dispatched by pc_from_corr before the level loop and cannot be "
            "selected per level"
        )
    discrete = test is not None and getattr(test, "kind", "gaussian") == "discrete"
    if discrete:
        remap = {"S": "G2", "E": "G2", "auto": "G2-kernel",
                 "S-kernel": "G2-kernel", "G2": "G2", "G2-kernel": "G2-kernel"}
        if name not in remap:
            raise ValueError(
                f"engine {name!r} has no discrete-test path: the dense ℓ=1 "
                "cube and the grid-resident sweep are partial-correlation "
                "layouts. Use S/auto (remapped onto the G2 engines) or name "
                "G2/G2-kernel directly."
            )
        return remap[name]
    if name in DISCRETE_ENGINES:
        raise ValueError(
            f"engine {name!r} runs the discrete G² test and needs a discrete "
            "CITest (pass test='discrete' with categorical samples); the "
            "Gaussian path uses S/E/S-kernel/S-grid/L1-dense/auto."
        )
    if name == "auto":
        return "L1-dense" if ell == 1 else "S-kernel"
    if name == "L1-dense" and ell != 1:
        return "S"  # the dense cube only exists at ℓ=1
    return name


def run_level(
    c,
    adj,
    sep,
    ell: int,
    tau: float,
    engine="auto",
    cell_budget: int = DEFAULT_CELL_BUDGET,
    bucket: bool = True,
    chunk_fn_s=None,
    chunk_fn_e=None,
    pipeline_depth: int = 1,
    test=None,
):
    """Dispatch one PC-stable level to the resolved engine.

    Same contract as levels.run_level: returns (adj, sep, stats) with
    stats["engine"] naming the concrete path taken. ``test`` (core/cit
    CITest; None = Gaussian) routes the level: Gaussian tests read a
    correlation matrix from ``c`` and a Fisher-z τ from ``tau``; a
    discrete test carries its DiscreteStats pytree in the c slot and α in
    the tau slot, dispatching levels.chunk_g2 through the same planner,
    worklist and commit layer.

    pipeline_depth ≥ 2 enables split tests/commit dispatch-ahead on the jnp
    "S" worklist (levels.chunk_s_tests/chunk_s_commit) — bit-identical
    results at any depth. Fused engines (E, the Pallas chunk functions, the
    dense ℓ=1 cube) run depth-1 regardless; the distributed driver
    (core/distributed.run_level_sharded) pipelines every layout.
    """
    name = resolve(engine, ell, test)
    if name in DISCRETE_ENGINES:
        test.check_level(ell)
        # the worklist's dominant array is the (m, n, T, n′) joint-code
        # gather — rescale the budget so plan_level's ℓ²-cell model yields
        # the chunk length the m-cell reality affords
        budget = max(1, int(cell_budget) * max(ell, 1) ** 2 // max(int(test.m), 1))
        fn = functools.partial(L.chunk_g2, r=int(test.r),
                               use_kernel=name == "G2-kernel")
        adj, sep, st = L.run_level(
            c, adj, sep, ell, tau, engine="S", cell_budget=budget,
            chunk_fn_s=fn, bucket=bucket,
        )
        st["engine"] = name
        st["test"] = "discrete"
    elif name == "L1-dense":
        adj, sep, st = _run_level_dense_l1(c, adj, sep, tau)
    elif name == "S-kernel":
        from repro.kernels.ops import chunk_s_kernel

        if ell >= 2:
            adj, sep, st = L.run_level_classes(c, adj, sep, ell, tau,
                                               cell_budget=cell_budget, bucket=bucket)
        else:
            adj, sep, st = L.run_level(
                c, adj, sep, ell, tau, engine="S", cell_budget=cell_budget,
                chunk_fn_s=chunk_fn_s or chunk_s_kernel, bucket=bucket,
            )
        st["engine"] = "S-kernel"
    elif name == "S-grid":
        from repro.kernels.ops import chunk_s_grid

        # the grid engine streams the rank axis through the kernel grid, so
        # a launch's HBM cost is the gather alone — raise the default
        # per-dispatch budget to the per-launch one (an explicit budget is
        # respected, e.g. to force multi-launch levels in tests)
        budget = (L.GRID_CELL_BUDGET if cell_budget == DEFAULT_CELL_BUDGET
                  else cell_budget)
        adj, sep, st = L.run_level(
            c, adj, sep, ell, tau, engine="S", cell_budget=budget,
            chunk_fn_s=chunk_fn_s or chunk_s_grid, bucket=bucket,
        )
        st["engine"] = "S-grid"
    else:
        adj, sep, st = L.run_level(
            c, adj, sep, ell, tau, engine=name, cell_budget=cell_budget,
            chunk_fn_s=chunk_fn_s, chunk_fn_e=chunk_fn_e, bucket=bucket,
            pipeline_depth=pipeline_depth,
        )
    # the ONE single-device seam where per-level counters enter the metrics
    # registry (the sharded twin lives in distributed.run_level_sharded);
    # levels.run_level stays registry-free so nothing double-counts
    obs.record_level_stats(st, level=ell, layout="single")
    return adj, sep, st


def batch_run(cs, m, *, mesh=None, level_sync: bool = False, **kw):
    """Dispatch a many-graph workload through the whole-run "scan" engine.

    cs: (B, n, n) fp32 correlation matrices; m: sample count behind them
    (sets the Fisher-z thresholds). mesh (core/sharding.py flat 1-D mesh)
    shards the leading batch axis with ``batch_spec`` — the same compiled
    program runs per device over its B/n_dev local graphs (B % n_dev ≠ 0
    is padded with identity-correlation no-op graphs and trimmed from every
    output); None keeps everything on one device.

    level_sync=True routes through scan_levels_batch (one host sync per
    level for the whole — possibly sharded — batch, tight widths found on
    the fly) and returns (ScanResult, schedule); otherwise pc_scan_batch
    (zero level syncs) returns a ScanResult, whose fields carry the leading
    B axis: adj/cpdag (B,n,n) bool, sepsets (B,n,n,Lmax) int32, ok (B,)
    exactness certificates, max_degs (B, max_level) int32.

    Parity guarantee: results are bit-identical across both routes, any
    mesh, and the single-device "S" engine up to the static level cap
    whenever ``ok`` is True (tests/test_sharding.py, tests/test_batch.py).
    """
    from repro.batch.scan_pc import pc_scan_batch, scan_levels_batch

    if level_sync:
        return scan_levels_batch(cs, m, mesh=mesh, **kw)
    return pc_scan_batch(cs, m, mesh=mesh, **kw)


def _run_level_dense_l1(c, adj, sep, tau):
    """ℓ=1 as ONE fused dense kernel launch + commit — no rank chunking, no
    M2 gathers, no host loop (the paper's dominant level, Fig. 6)."""
    from repro.kernels.ops import level1_dense

    with obs.current().span("degree", level=1):
        npr = int(obs.fetch(jnp.max(jnp.sum(adj, axis=1)),
                            site="engines.dense_l1_degree"))
    if npr - 1 < 1:
        return adj, sep, {"skipped": True, "chunks": 0, "dispatches": 0,
                          "npr": npr, "engine": "L1-dense"}
    _removed, kwin = level1_dense(c, adj, tau)
    adj_new, sep_new = L.commit_dense_l1(adj, sep, kwin)
    return adj_new, sep_new, {
        "skipped": False, "chunks": 1, "dispatches": 1, "npr": npr,
        "npr_bucket": npr, "total_sets": npr, "engine": "L1-dense",
        "dense": True,
    }
