"""Layer 2 — jaxpr contract analyzers (``RPR1xx``).

Abstractly traces the public entry points (the per-engine chunk functions,
the kernel wrappers, and the whole-run traced scan) with
``jax.make_jaxpr`` on small shape-representative inputs and walks the
resulting jaxprs:

  RPR101  f32→f64 promotion: traced under ``enable_x64`` (where a silent
          weak-type promotion becomes a real float64 aval instead of being
          truncated away), every float aval in the program must stay f32.
          Integer widening to int64 is the *intended* rank regime and is
          allowed.
  RPR102  callback primitives (``pure_callback`` / ``io_callback`` /
          ``debug_callback`` / ``debug_print``) in hot paths — every one
          is a host round-trip per dispatch.
  RPR103  dispatch contract: (a) the number of ``pallas_call`` primitives
          in each entry point's jaxpr equals the declared kernel count —
          a refactor that hides an extra kernel launch inside a "single
          dispatch" engine fails here; (b) ``stats["dispatches"]`` from a
          live run obeys the PR-5 planner arithmetic
          (``chunks == ceil(total/n_chunk)``, ×2 when pipelined).
  RPR104  combinadics rank capacity: for every (n′, ℓ) the planner
          accepts, the worst commit key ``C(n′,ℓ)·2+bit`` must fit the
          rank dtype's guarded range (``levels._imax``) — the symbolic
          bound that keeps clipped binomial-table ranks from aliasing.

The analyzers are injectable (pass your own ``fn``/``plan_fn``) so the
test suite can aim them at deliberately-broken fixtures.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable

from .findings import Finding, register_rule

RPR101 = register_rule("RPR101", "f32→f64 promotion inside a traced entry point")
RPR102 = register_rule("RPR102", "host-callback primitive in a hot traced path")
RPR103 = register_rule("RPR103", "dispatch count breaks the stats/planner contract")
RPR104 = register_rule("RPR104", "combinadics commit keys exceed rank-dtype capacity")

CALLBACK_PRIMS = {"pure_callback", "io_callback", "debug_callback", "debug_print"}


# --------------------------------------------------------------------- walk
def _sub_jaxprs(params: dict):
    from jax.extend.core import ClosedJaxpr as closed
    from jax.extend.core import Jaxpr as open_

    for v in params.values():
        stack = [v]
        while stack:
            item = stack.pop()
            if isinstance(item, closed):
                yield item.jaxpr
            elif isinstance(item, open_):
                yield item
            elif isinstance(item, (list, tuple)):
                stack.extend(item)


def iter_eqns(jaxpr):
    """Every equation in a jaxpr, recursing through pjit/scan/cond bodies."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub)


def trace(fn: Callable, *args, **kwargs):
    """``jax.make_jaxpr`` under x64 so weak-type promotion is observable.

    Keyword args are bound with ``functools.partial`` first: make_jaxpr
    traces kwargs as dynamic inputs, which would turn static config ints
    (``ell``, ``n_chunk``, ...) into tracers and break the inner jits."""
    import functools

    import jax

    if kwargs:
        fn = functools.partial(fn, **kwargs)
    with jax.enable_x64(True):
        return jax.make_jaxpr(fn)(*args).jaxpr


# ------------------------------------------------------------------ RPR101/2
def promotion_findings(fn, *args, name: str = "", path: str = "src/repro",
                       **kwargs) -> list[Finding]:
    """Flag any float64 aval produced anywhere in fn's jaxpr (traced under
    x64 with f32 inputs: a weak-type promotion becomes visible f64)."""
    import numpy as np

    name = name or getattr(fn, "__name__", str(fn))
    jaxpr = trace(fn, *args, **kwargs)
    hits = []
    for eqn in iter_eqns(jaxpr):
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            dt = getattr(aval, "dtype", None)
            if dt is not None and dt == np.float64:
                hits.append(eqn.primitive.name)
    if hits:
        uniq = sorted(set(hits))
        return [Finding(
            code=RPR101, path=path, line=0,
            message=f"`{name}` promotes to float64 at {len(hits)} site(s) "
                    f"(primitives: {', '.join(uniq[:6])}) — the bit-parity "
                    "contract requires the f32 pipeline end to end",
            context=name, detail="f64-promotion",
        )]
    return []


def callback_findings(fn, *args, name: str = "", path: str = "src/repro",
                      **kwargs) -> list[Finding]:
    name = name or getattr(fn, "__name__", str(fn))
    jaxpr = trace(fn, *args, **kwargs)
    hits = sorted({
        eqn.primitive.name for eqn in iter_eqns(jaxpr)
        if eqn.primitive.name in CALLBACK_PRIMS
    })
    return [
        Finding(
            code=RPR102, path=path, line=0,
            message=f"`{name}` stages host callback primitive `{p}` — a "
                    "host round-trip on every dispatch of a hot path",
            context=name, detail=p,
        )
        for p in hits
    ]


# -------------------------------------------------------------------- RPR103
def count_pallas_calls(fn, *args, **kwargs) -> int:
    jaxpr = trace(fn, *args, **kwargs)
    return sum(1 for eqn in iter_eqns(jaxpr) if eqn.primitive.name == "pallas_call")


def kernel_count_findings(fn, expected: int, *args, name: str = "",
                          path: str = "src/repro", **kwargs) -> list[Finding]:
    name = name or getattr(fn, "__name__", str(fn))
    got = count_pallas_calls(fn, *args, **kwargs)
    if got != expected:
        return [Finding(
            code=RPR103, path=path, line=0,
            message=f"`{name}` stages {got} pallas_call primitive(s); the "
                    f"declared dispatch contract is {expected} — a hidden "
                    "kernel launch changes the per-level dispatch count",
            context=name, detail=f"pallas_calls:{got}!={expected}",
        )]
    return []


def stats_contract_findings(level_stats, path: str = "<run>") -> list[Finding]:
    """Verify a live run's per-level stats obey the PR-5 planner arithmetic:
    ``chunks == ceil(total_sets/n_chunk)`` and ``dispatches == chunks ×
    (2 if pipelined else 1)``; a degree-class level
    (levels.run_level_classes) sums its classes' chunks and adds its one
    commit program. ``level_stats``: iterable of stats dicts
    (PCRun.level_stats)."""
    out = []
    for i, st in enumerate(level_stats):
        if not isinstance(st, dict) or st.get("skipped", False):
            continue
        ctx = f"level[{i}]:{st.get('engine', '?')}"
        total, n_chunk = st.get("total_sets"), st.get("n_chunk")
        chunks, disp = st.get("chunks"), st.get("dispatches")
        if total is not None and n_chunk:
            want_chunks = -(-total // n_chunk)
            if chunks != want_chunks:
                out.append(Finding(
                    code=RPR103, path=path, line=0,
                    message=f"{ctx}: {chunks} chunks for {total} sets at "
                            f"n_chunk={n_chunk} (expected {want_chunks})",
                    context=ctx, detail="chunks",
                ))
        if "classes" in st and chunks != sum(k["chunks"] for k in st["classes"]):
            out.append(Finding(
                code=RPR103, path=path, line=0,
                message=f"{ctx}: {chunks} chunks but its classes ran "
                        f"{sum(k['chunks'] for k in st['classes'])}",
                context=ctx, detail="chunks",
            ))
        if chunks is not None and disp is not None:
            mult = 2 if st.get("pipeline_depth", 1) > 1 else 1
            if disp != chunks * mult + ("classes" in st):
                out.append(Finding(
                    code=RPR103, path=path, line=0,
                    message=f"{ctx}: dispatches={disp} but chunks={chunks} "
                            f"with pipeline multiplier {mult} — the "
                            "stats['dispatches'] contract is broken",
                    context=ctx, detail="dispatches",
                ))
    return out


# -------------------------------------------------------------------- RPR104
def rank_capacity_findings(
    plan_fn=None, imax: int | None = None, n_max: int = 96, l_max: int = 8,
    path: str = "src/repro/core/levels.py",
) -> list[Finding]:
    """Exhaustively sweep (n′, ℓ) and assert: every plan the planner RETURNS
    keeps (a) the worst commit key ``(total−1)·2+1`` strictly under the
    ``imax`` sentinel (``levels._global_commit`` decides removals with
    ``final_key < imax``, so a key ≥ imax silently drops a real winner) and
    (b) every rank a chunk touches (< total + n_chunk) exact in the clipped
    binomial table. Plans the planner refuses (ValueError) are safe."""
    from repro.core import levels as L

    plan_fn = plan_fn or L.plan_level
    imax = int(L._imax()) if imax is None else int(imax)
    out = []
    for npr in range(2, n_max + 1):
        for ell in range(1, min(npr, l_max) + 1):
            try:
                _, n_chunk, total = plan_fn(npr, ell, n_rows=8)
            except ValueError:
                continue  # loud refusal — the guard did its job
            worst_key = (total - 1) * 2 + 1
            if worst_key >= imax:
                out.append(Finding(
                    code=RPR104, path=path, line=0,
                    message=f"plan_level({npr}, {ell}) accepts total={total} "
                            f"but the worst commit key {worst_key} reaches "
                            f"the imax sentinel {imax} — winners with rank ≥ "
                            "imax/2 would silently fail to commit",
                    context="plan_level", detail=f"key-overflow:{npr},{ell}",
                ))
                continue
            if n_chunk > 1 and total + n_chunk > imax:
                out.append(Finding(
                    code=RPR104, path=path, line=0,
                    message=f"plan_level({npr}, {ell}) chunk reaches rank "
                            f"{total + n_chunk} past the clipped binomial "
                            f"table capacity {imax}",
                    context="plan_level", detail=f"table-overflow:{npr},{ell}",
                ))
    return out


# ------------------------------------------------------- entry-point registry
@dataclass(frozen=True)
class Entry:
    name: str
    build: Callable  # () -> (fn, args tuple, kwargs dict)
    pallas_calls: int  # declared dispatch-primitive contract
    path: str


def _gauss_chunk_args(n=16, npr=8, ell=2, n_chunk=8):
    import jax.numpy as jnp

    from repro.core.levels import _rank_dtype

    c = jnp.eye(n, dtype=jnp.float32)
    adj = jnp.ones((n, n), bool) & ~jnp.eye(n, dtype=bool)
    sep = jnp.full((n, n, 8), -1, jnp.int32)
    compact = jnp.zeros((n, npr), jnp.int32)
    counts = jnp.full((n,), npr, jnp.int32)
    t0 = jnp.asarray(0, _rank_dtype())
    tau = jnp.asarray(0.5, jnp.float32)
    return c, adj, sep, compact, counts, t0, tau, dict(
        ell=ell, n_chunk=n_chunk, n_max=npr
    )


def entry_points() -> list[Entry]:
    """The traced surface the parity matrix rests on, with each entry's
    declared pallas_call count. Traced on small shape-representative
    inputs; adding an engine means adding a row here (test_analysis pins
    the registry against the engine registry)."""

    def chunk_s():
        from repro.core import levels as L
        c, adj, sep, compact, counts, t0, tau, kw = _gauss_chunk_args()
        return L.chunk_s, (c, adj, sep, compact, counts, t0, tau), kw

    def chunk_e():
        from repro.core import levels as L
        c, adj, sep, compact, counts, t0, tau, kw = _gauss_chunk_args()
        return L.chunk_e, (c, adj, sep, compact, counts, t0, tau), kw

    def chunk_s_tests():
        from repro.core import levels as L
        c, adj, sep, compact, counts, t0, tau, kw = _gauss_chunk_args()
        return L.chunk_s_tests, (c, adj, compact, counts, t0, tau), kw

    def chunk_s_kernel():
        from repro.kernels import ops
        c, adj, sep, compact, counts, t0, tau, kw = _gauss_chunk_args()
        return ops.chunk_s_kernel, (c, adj, sep, compact, counts, t0, tau), kw

    def chunk_s_grid():
        from repro.kernels import ops
        c, adj, sep, compact, counts, t0, tau, kw = _gauss_chunk_args()
        return ops.chunk_s_grid, (c, adj, sep, compact, counts, t0, tau), kw

    def chunk_s_kernel_class():
        import jax.numpy as jnp

        from repro.core import levels as L
        from repro.kernels import ops
        c, adj, sep, _, _, t0, tau, kw = _gauss_chunk_args()
        n = adj.shape[0]
        key = jnp.full((n, n), L._imax(), L._rank_dtype())
        rows = jnp.asarray([0, 3, 5, -1, -1, -1, -1, -1], jnp.int32)
        return ops.chunk_s_kernel_class, (c, adj, sep, key, rows, t0, tau), kw

    def chunk_g2():
        import jax.numpy as jnp

        from repro.core import levels as L
        from repro.core.cit import DiscreteStats
        _, adj, sep, compact, counts, t0, _, kw = _gauss_chunk_args()
        stats = DiscreteStats(
            codes=jnp.zeros((32, 16), jnp.int32),
            arities=jnp.full((16,), 2, jnp.int32),
        )
        alpha = jnp.asarray(0.01, jnp.float32)
        kw = dict(kw, r=2, use_kernel=False)
        return L.chunk_g2, (stats, adj, sep, compact, counts, t0, alpha), kw

    def chunk_g2_kernel():
        fn, args, kw = chunk_g2()
        return fn, args, dict(kw, use_kernel=True)

    def level1_dense():
        import jax.numpy as jnp

        from repro.kernels import ops
        c = jnp.eye(256, dtype=jnp.float32)
        adj = jnp.ones((256, 256), jnp.uint8)
        return ops.level1_dense, (c, adj, jnp.asarray(0.5, jnp.float32)), {}

    def level0():
        import jax.numpy as jnp

        from repro.kernels import ops
        return ops.level0, (jnp.eye(256, dtype=jnp.float32),
                            jnp.asarray(0.5, jnp.float32)), {}

    def correlation():
        import jax.numpy as jnp

        from repro.kernels import ops
        return ops.correlation, (jnp.ones((512, 256), jnp.float32),), {}

    def gsq_cells():
        import jax.numpy as jnp

        from repro.kernels.gsq import gsq_cells as fn
        return fn, (jnp.zeros((64, 16), jnp.int32),), dict(r=2, q=2)

    def pc_scan():
        import jax.numpy as jnp

        from repro.batch.scan_pc import pc_scan as fn

        def run(c, taus):
            return fn(c, m=200, max_level=2, n_prime=4, taus=taus)

        c = jnp.eye(16, dtype=jnp.float32)
        taus = jnp.asarray([0.5, 0.4, 0.3], jnp.float32)
        run.__name__ = "pc_scan"
        return run, (c, taus), {}

    k, c, b = "src/repro/kernels", "src/repro/core", "src/repro/batch"
    return [
        Entry("chunk_s", chunk_s, 0, f"{c}/levels.py"),
        Entry("chunk_e", chunk_e, 0, f"{c}/levels.py"),
        Entry("chunk_s_tests", chunk_s_tests, 0, f"{c}/levels.py"),
        Entry("chunk_g2", chunk_g2, 0, f"{c}/levels.py"),
        Entry("chunk_g2_kernel", chunk_g2_kernel, 1, f"{c}/levels.py"),
        Entry("chunk_s_kernel", chunk_s_kernel, 2, f"{k}/ops.py"),
        Entry("chunk_s_grid", chunk_s_grid, 1, f"{k}/ops.py"),
        Entry("chunk_s_kernel_class", chunk_s_kernel_class, 2, f"{k}/ops.py"),
        Entry("level1_dense", level1_dense, 1, f"{k}/ops.py"),
        Entry("level0", level0, 1, f"{k}/ops.py"),
        Entry("correlation", correlation, 1, f"{k}/ops.py"),
        Entry("gsq_cells", gsq_cells, 1, f"{k}/gsq.py"),
        Entry("pc_scan", pc_scan, 0, f"{b}/scan_pc.py"),
    ]


def check_entry_points(entries: list[Entry] | None = None) -> list[Finding]:
    """RPR101 + RPR102 + RPR103(a) over the registered entry points."""
    out = []
    for e in (entries if entries is not None else entry_points()):
        fn, args, kwargs = e.build()
        out += promotion_findings(fn, *args, name=e.name, path=e.path, **kwargs)
        out += callback_findings(fn, *args, name=e.name, path=e.path, **kwargs)
        out += kernel_count_findings(
            fn, e.pallas_calls, *args, name=e.name, path=e.path, **kwargs
        )
    return out


def check_dispatch_contract(engines=("S", "E", "S-kernel", "S-grid"),
                            n: int = 24, m: int = 400) -> list[Finding]:
    """RPR103(b): run each engine on a small concrete workload and verify
    the published level stats against the planner arithmetic."""
    import numpy as np

    # `repro.core` re-exports a *function* named `pc`, shadowing the
    # submodule attribute — import the symbol, not the module
    from repro.core.pc import pc_from_corr

    rng = np.random.default_rng(0)
    x = rng.normal(size=(m, n)).astype(np.float32)
    c = np.corrcoef(x, rowvar=False).astype(np.float32)
    out = []
    for eng in engines:
        run = pc_from_corr(c, m, alpha=0.05, engine=eng, max_level=2)
        out += stats_contract_findings(
            run.level_stats, path=f"<pc_from_corr engine={eng}>"
        )
    return out


def all_findings(deep: bool = True) -> list[Finding]:
    """Every Layer-2 check. ``deep=False`` skips the concrete-run dispatch
    contract (used by fast unit tests; CI runs deep)."""
    out = check_entry_points()
    out += rank_capacity_findings()
    if deep:
        out += check_dispatch_contract()
    return out


def expected_chunks(total: int, n_chunk: int) -> int:
    return -(-total // n_chunk)


# re-export for check_regression's structural gate
__all__ = [
    "all_findings", "check_entry_points", "check_dispatch_contract",
    "stats_contract_findings", "rank_capacity_findings", "count_pallas_calls",
    "kernel_count_findings", "promotion_findings", "callback_findings",
    "entry_points", "iter_eqns", "trace", "expected_chunks", "Entry",
    "CALLBACK_PRIMS",
]

# keep the import for type checkers that resolve `math` in annotations
_ = math
