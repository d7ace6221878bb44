"""Top-level PC-stable driver — the public API of the paper's contribution.

    result = pc(x_samples, alpha=0.01)                    # kernel-backed auto
    result = pc_from_corr(c, m, alpha=0.01, engine="S")   # force jnp cuPC-S

Mirrors paper Algorithm 2: host loop over levels; level 0 fused; levels ≥ 1
dispatched through the engine registry (core/engines.py) — by default the
"auto" hybrid: the fused dense ℓ=1 Pallas kernel, then the cholinv+cisweep
cuPC-S kernel pipeline for ℓ≥2 (interpret mode off-TPU). The adjacency is
(re-)compacted at every level boundary with bucketed static shapes so jit
caches persist across levels. Orientation (v-structures + Meek) produces
the CPDAG.

engine="scan" replaces the host level loop wholesale with the fixed-shape
traced program (repro/batch/scan_pc.py) — bit-identical results up to its
static level cap, and the formulation that batches over many graphs
(repro/batch/ensemble.py bootstraps it B-wide in one dispatch).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

from . import engines as E
from . import levels as L  # noqa: F401  (re-export seam for tests/monkeypatch)
from . import validate as V
from .cit import (DiscreteCITest, GaussianCITest,  # noqa: F401
                  correlation_from_samples, encode_discrete, resolve_citest)
from .combinadics import MAX_LEVEL
from .orient import cpdag_from_skeleton


@dataclass
class PCRun:
    adj: np.ndarray  # skeleton (n,n) bool
    cpdag: np.ndarray  # digraph (n,n) bool
    sepsets: np.ndarray  # (n,n,Lmax) int32, -1 padded
    levels_run: int
    level_stats: list = field(default_factory=list)
    timings_s: dict = field(default_factory=dict)
    counts: dict = field(default_factory=dict)  # tracer counts, e.g. host_syncs

    def sepset_dict(self) -> dict:
        """{(i, j) i<j → tuple of separator ids} for removed edges with a
        recorded sepset (level-0 removals carry the -2 sentinel and are
        excluded — their sepset is empty by definition).

        Vectorised: one upper-triangle mask pass selects the entries; Python
        only iterates over the (sparse) selected pairs, not all n² cells.
        """
        n = self.adj.shape[0]
        iu, ju = np.triu_indices(n, 1)
        srows = self.sepsets[iu, ju]  # (P, Lmax)
        has_ids = (srows >= 0).any(axis=1)
        keep = ~self.adj[iu, ju] & (has_ids | (srows[:, 0] != -2))
        return {
            (int(i), int(j)): tuple(int(v) for v in row[row >= 0])
            for i, j, row in zip(iu[keep], ju[keep], srows[keep])
        }


def pc_from_corr(
    c,
    m: int,
    alpha: float = 0.01,
    engine="auto",
    max_level: int | None = None,
    sepset_depth: int = 8,
    cell_budget: int = E.DEFAULT_CELL_BUDGET,
    orient: bool = True,
    chunk_fn_s=None,
    chunk_fn_e=None,
    bucket: bool = True,
    pipeline_depth: int = 1,
    validate: bool = True,
    test=None,
) -> PCRun:
    """Run PC-stable given a correlation matrix c (n,n) and sample count m.

    engine: a name from engines.ENGINE_NAMES or callable(ell)->name;
    bucket=False disables n′/chunk bucketing (one jit compile per exact
    max-degree — the legacy behaviour, kept for the compile-count probe);
    pipeline_depth ≥ 2 keeps that many rank-chunks' tests in flight per
    level on the jnp "S" worklist (bit-identical — see engines.run_level).

    validate=True (default) runs core/validate.py admission checks on
    (c, m) and raises a typed ValidationError on NaN/Inf, a non-correlation
    matrix, or an m too small for the requested test depth — a NaN in C
    otherwise propagates silently (NaN comparisons keep every affected
    edge). m < n warns but runs: the paper's gene-expression datasets live
    in that regime.

    test: None/"gaussian"/GaussianCITest only — a correlation matrix IS
    the Gaussian sufficient statistic; the discrete G² test needs raw
    level codes and routes through ``pc(x, test="discrete")``.
    """
    test = resolve_citest(test, m, alpha)
    if test.kind != "gaussian":
        raise ValueError(
            f"pc_from_corr runs the Gaussian partial-correlation test; a "
            f"{test.kind!r} CITest needs raw samples — call "
            "pc(x, test=...) instead"
        )
    tracer = obs.run_tracer("pc_from_corr")
    with tracer.activate(), tracer.span("total", engine=str(engine)):
        if validate:
            with tracer.span("validate"):
                V.validate_corr(c, m, max_level=max_level)
        with tracer.span("upload"):
            c = jnp.asarray(c, jnp.float32)
        run = _pc_gaussian(
            c, m, alpha, test, engine=engine, max_level=max_level,
            sepset_depth=sepset_depth, cell_budget=cell_budget,
            orient=orient, chunk_fn_s=chunk_fn_s, chunk_fn_e=chunk_fn_e,
            bucket=bucket, pipeline_depth=pipeline_depth, tracer=tracer,
        )
    return _finish(run, tracer, "pc_from_corr", engine)


def _pc_gaussian(c, m, alpha, test, *, tracer, engine="auto",
                 max_level=None, sepset_depth: int = 8,
                 cell_budget: int = E.DEFAULT_CELL_BUDGET,
                 orient: bool = True, chunk_fn_s=None, chunk_fn_e=None,
                 bucket: bool = True, pipeline_depth: int = 1) -> PCRun:
    """The Gaussian run from a device-resident f32 C, inside the caller's
    open ``total`` span: the scan program or the host level loop."""
    if E.is_whole_run(engine):
        return _pc_run_scan(
            c, m, alpha=alpha, max_level=max_level,
            sepset_depth=sepset_depth, cell_budget=cell_budget,
            orient=orient, tracer=tracer,
        )
    lmax = min(max_level if max_level is not None else MAX_LEVEL,
               sepset_depth)
    return _pc_run_host_loop(
        c, test, engine=engine, lmax=lmax,
        sepset_depth=sepset_depth, cell_budget=cell_budget,
        orient=orient, bucket=bucket, chunk_fn_s=chunk_fn_s,
        chunk_fn_e=chunk_fn_e, pipeline_depth=pipeline_depth,
        tracer=tracer,
    )


def _finish(run: PCRun, tracer, driver: str, engine) -> PCRun:
    """Fill the run's ``timings_s`` and ``counts`` from its tracer and write
    the journal's closing ``run`` record."""
    run.timings_s = tracer.timings()
    run.counts = tracer.counts()
    tracer.finish(driver=driver, engine=str(engine),
                  n=int(run.adj.shape[0]), levels_run=run.levels_run)
    return run


def _pc_run_host_loop(stats, test, *, engine, lmax, sepset_depth,
                      cell_budget, orient, bucket=True, chunk_fn_s=None,
                      chunk_fn_e=None, pipeline_depth=1, tracer):
    """The per-level host loop of Algorithm 2, instrumented span-per-level,
    generalised over the CITest seam: ``stats`` is whatever the test's
    sufficient statistic is (C for Gaussian — the pre-refactor calls are
    reproduced verbatim, so decisions are bit-identical — or DiscreteStats
    for G²), and the per-level scalar fed to the engines comes from
    ``test.tau(ell)`` (warn-level on insufficient samples: a validated
    entry point only lands here past the validated depth, where a loud
    skip-grade τ beats aborting a mostly-finished run).

    Each span syncs the level's adjacency at exit, so span durations cover
    device time — exactly what the old block_until_ready + perf_counter
    pairs measured. The max-degree read before each level runs in a
    ``degree`` span and the result download in ``readback``; every
    blocking read goes through ``obs.fetch``."""
    # C is (n, n); DiscreteStats carries (m, n) codes
    n = int(stats.codes.shape[1] if hasattr(stats, "codes")
            else stats.shape[0])
    with tracer.span("level0", level=0) as sp:
        adj = test.level0(stats, test.tau(0, insufficient="warn"))
        # sepset sentinel: -2 in slot 0 = "removed with empty sepset (level 0)"
        sep = jnp.full((n, n, sepset_depth), -1, jnp.int32)
        sep = sep.at[:, :, 0].set(jnp.where(adj, -1, -2))
        sp.sync(adj)

    stats_out = []
    ell = 1
    while ell <= lmax:
        with tracer.span("degree", level=ell):
            max_deg = int(obs.fetch(jnp.max(jnp.sum(adj, axis=1)),
                                    site="pc.degree"))
        if max_deg - 1 < ell:
            break
        with tracer.span(f"level{ell}", level=ell) as sp:
            adj, sep, st = E.run_level(
                stats, adj, sep, ell, test.tau(ell, insufficient="warn"),
                engine=engine, cell_budget=cell_budget, bucket=bucket,
                chunk_fn_s=chunk_fn_s, chunk_fn_e=chunk_fn_e,
                pipeline_depth=pipeline_depth, test=test,
            )
            sp.sync(adj).set(**{k: st[k] for k in
                                ("engine", "chunks", "dispatches",
                                 "total_sets", "npr_bucket")
                                if k in st})
        stats_out.append({"level": ell, **st})
        ell += 1

    with tracer.span("orient") as sp:
        if orient:
            max_deg = int(obs.fetch(jnp.max(jnp.sum(adj, axis=1)),
                                    site="pc.orient_degree"))
            cpdag = cpdag_from_skeleton(adj, sep, n_prime=min(n, L.bucket_npr(max(max_deg, 1))))
        else:
            cpdag = adj
        sp.sync(cpdag)

    with tracer.span("readback"):
        adj_h = np.asarray(obs.fetch(adj, site="pc.readback"))
        cpdag_h = np.asarray(obs.fetch(cpdag, site="pc.readback"))
        sep_h = np.asarray(obs.fetch(sep, site="pc.readback"))
    return PCRun(adj=adj_h, cpdag=cpdag_h, sepsets=sep_h,
                 levels_run=ell - 1, level_stats=stats_out)


def _pc_run_scan(c, m, alpha, max_level, sepset_depth, cell_budget, orient,
                 tracer, test=None):
    """engine="scan": the whole run as the fixed-shape traced program
    (repro/batch/scan_pc.py) packaged into the PCRun contract.

    max_level=None uses the scan path's static DEFAULT_MAX_LEVEL (deeper
    levels need an explicit cap — each one is unrolled into the program);
    results are bit-identical to engine="S" at the same cap. levels_run
    reports the levels that actually had work (the host driver's stopping
    rule applied to the recorded per-level max degrees), not the cap.
    """
    import warnings

    from repro.batch.scan_pc import DEFAULT_MAX_LEVEL, pc_scan

    if max_level is None and sepset_depth > DEFAULT_MAX_LEVEL:
        warnings.warn(
            f"engine='scan' runs a STATIC level cap of {DEFAULT_MAX_LEVEL} "
            "by default, while the host-loop engines iterate until "
            "convergence — on deep graphs the skeletons differ. Pass "
            "max_level explicitly to choose the cap (and silence this).",
            stacklevel=4,
        )
    lmax = min(DEFAULT_MAX_LEVEL if max_level is None else max_level, sepset_depth)
    with tracer.span("scan", max_level=lmax) as sp:
        res = pc_scan(
            c, m, alpha=alpha, max_level=lmax, sepset_depth=sepset_depth,
            cell_budget=cell_budget, orient=orient, test=test,
        )
        sp.sync(res.cpdag)
    with tracer.span("readback"):
        degs = np.asarray(obs.fetch(res.max_degs, site="scan.max_degs"))
        adj_h = np.asarray(obs.fetch(res.adj, site="pc.readback"))
        cpdag_h = np.asarray(obs.fetch(res.cpdag, site="pc.readback"))
        sep_h = np.asarray(obs.fetch(res.sepsets, site="pc.readback"))
    # the host driver stops at the first level with max_deg - 1 < ell
    levels_run = 0
    for ell in range(1, lmax + 1):
        if degs[ell - 1] - 1 < ell:
            break
        levels_run = ell
    return PCRun(
        adj=adj_h,
        cpdag=cpdag_h,
        sepsets=sep_h,
        levels_run=levels_run,
        level_stats=[{"level": ell, "engine": "scan",
                      "skipped": ell > levels_run,
                      "npr": int(degs[ell - 1]), "max_level_static": lmax}
                     for ell in range(1, lmax + 1)],
    )


def _pc_discrete(
    x,
    test,
    *,
    tracer,
    engine="auto",
    max_level=None,
    sepset_depth: int = 8,
    cell_budget: int = E.DEFAULT_CELL_BUDGET,
    orient: bool = True,
    bucket: bool = True,
    chunk_fn_s=None,
    chunk_fn_e=None,
    pipeline_depth: int = 1,
    validate: bool = True,
) -> PCRun:
    """The discrete G² route of ``pc()``, inside its open ``total`` span:
    encode level codes, rebind the test's (m, r) to the data (the run-wide
    max arity is a static shape parameter — see DiscreteCITest), then drive
    the SAME host loop / scan program the Gaussian path uses, with
    DiscreteStats riding the stats slot."""
    if validate:
        with tracer.span("validate"):
            V.validate_discrete(x, max_level=max_level)
    with tracer.span("encode"):
        stats, r_max = encode_discrete(x)
    test = dataclasses.replace(
        test, m=int(stats.codes.shape[0]), r=max(int(test.r), r_max)
    )
    if max_level is None:
        # cap where the contingency table still fits; an EXPLICIT deeper
        # max_level is a user claim we reject loudly via check_level
        lmax = min(MAX_LEVEL, sepset_depth, test.max_supported_level())
    else:
        lmax = min(max_level, sepset_depth)
    test.check_level(lmax)
    if E.is_whole_run(engine):
        if max_level is None:
            # scan's static default cap, still bounded by the table cap
            from repro.batch.scan_pc import DEFAULT_MAX_LEVEL

            lmax = min(lmax, DEFAULT_MAX_LEVEL)
        return _pc_run_scan(
            stats, test.m, alpha=test.alpha, max_level=lmax,
            sepset_depth=sepset_depth, cell_budget=cell_budget,
            orient=orient, tracer=tracer, test=test,
        )
    return _pc_run_host_loop(
        stats, test, engine=engine, lmax=lmax,
        sepset_depth=sepset_depth, cell_budget=cell_budget,
        orient=orient, bucket=bucket, chunk_fn_s=chunk_fn_s,
        chunk_fn_e=chunk_fn_e, pipeline_depth=pipeline_depth,
        tracer=tracer,
    )


def pc(
    x,
    alpha: float = 0.01,
    engine="auto",
    max_level: int | None = None,
    corr: str = "auto",
    validate: bool = True,
    test=None,
    **kw,
) -> PCRun:
    """Run PC-stable from raw samples x: (m, n).

    corr: "kernel" computes C on the tiled MXU kernel (kernels/corr.py),
    "jnp" uses the XLA reference; "auto" picks the kernel on TPU and jnp
    elsewhere (the interpreted kernel is exact but CPU-slow for large m·n²).

    test: None/"gaussian" (default, Fisher-z on the correlation matrix),
    "discrete" (contingency-table G²/χ² over integer level codes — x must
    be categorical; engines route to the G² worklist/kernel automatically),
    or a CITest instance. The Gaussian path through the test object is
    bit-identical to the pre-seam behaviour.

    validate=True (default) rejects NaN/Inf samples and constant columns
    with typed errors (core/validate.py) — both previously flowed through
    correlation_from_samples silently (a constant column becomes a row of
    fabricated zero correlations, i.e. universal independence). m < n warns
    but runs. validate=False restores the old trust-the-caller behaviour.
    The discrete route additionally demands non-negative integer codes
    (validate_discrete).

    One tracer covers the whole call: ``timings_s["total"]`` runs from the
    upload of x to the download of the results, and its children are
    ``upload``, ``validate``, ``corr``, ``level0``, ``degree``/``level<l>``
    pairs, ``orient`` and ``readback`` (docs/observability.md).
    """
    tracer = obs.run_tracer("pc")
    with tracer.activate(), tracer.span("total", engine=str(engine)):
        with tracer.span("upload"):
            x = jnp.asarray(x)
            t = resolve_citest(test, int(x.shape[0]), alpha)
            if t.kind != "discrete":
                x = x.astype(jnp.float32)  # f32 on the device even when x64 is on
        if t.kind == "discrete":
            if corr != "auto":
                raise ValueError(
                    "corr= selects a correlation backend; the discrete G² "
                    "test does not compute correlations"
                )
            run = _pc_discrete(x, t, engine=engine, max_level=max_level,
                               validate=validate, tracer=tracer, **kw)
        else:
            if validate:
                with tracer.span("validate"):
                    V.validate_samples(x, max_level=max_level)
            if corr not in ("auto", "kernel", "jnp"):
                raise ValueError(f"corr must be auto|kernel|jnp, got {corr!r}")
            use_kernel = corr == "kernel" or (
                corr == "auto" and jax.default_backend() == "tpu")
            with tracer.span("corr", kernel=use_kernel):
                if use_kernel:
                    from repro.kernels.ops import correlation as corr_kernel

                    c = corr_kernel(x)
                else:
                    c = correlation_from_samples(x)
            # samples already validated and C built in-house: no re-check
            run = _pc_gaussian(c, int(x.shape[0]), alpha, t, engine=engine,
                               max_level=max_level, tracer=tracer, **kw)
    return _finish(run, tracer, "pc", engine)
