#!/usr/bin/env python3
"""Run PC-stable end to end on a TPU through the public entry points, and
check the result.

    python chip_smoke.py                # one chip (the default)
    python chip_smoke.py --four-chips   # the row-sharded path on a 2x2 host

One chip, at paper Table 1's DREAM5-Insilico size (n=1643, m=850, d=0.05,
α=0.01; ``configs/cupc_datasets.py``), on samples from
``sample_gaussian_dag`` with a fixed seed, with the depth cut to
FULL_N_LEVELS levels:

  1. ``pc(engine="auto", corr="kernel")`` — the production path (dense
     ℓ=1 Pallas cube, then the cholinv + cisweep kernels);
  2. ``pc(engine="S-grid", corr="kernel")`` — the grid-resident kernel;
  3. ``pc(engine="S", corr="kernel")`` — the jnp/XLA engine, the reference.
     1 and 2 must give the same skeleton and sepsets as 3. The cut
     skeleton is not oriented: after level 1 it still has ~143k edges
     (408 at full depth), and orienting it took ~55 s per call on a v5e;
  4. at a reduced n (ORACLE_N) and full depth, the skeleton of each engine
     must equal the serial ``stable_ref`` oracle's, run on the host from
     the same C, and 1 and 2 must give the same sepsets and CPDAG as 3;
  5. the kernel correlation matrix must agree with a float64 host one;
  6. every kernel on the path must compile to Mosaic (``tpu_custom_call``
     in the compiled HLO), which proves no interpret mode.

``--four-chips`` runs only ``pc_distributed(engine="S-grid", shard_c=True,
shard_sep=True)`` on a 4-device mesh at S.cerevisiae's size (n=5361, m=63,
d=0.01) and compares it with the one-device S-grid result.

Every engine runs twice on the same data: the first call includes
compilation, the second is the wall time (``pc`` returns host arrays, so
the device work is complete), and the two must agree.
The last line of standard output is one JSON object naming the device.
Any mismatch exits non-zero; with no TPU the script exits non-zero before
doing any work. One process drives the chip(s); nothing is caught.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

#: Mosaic kernels lower to this custom call; interpret mode leaves none
_MOSAIC = re.compile(r'custom_call_target="tpu_custom_call"')
#: kernel C against a float64 host C (f32 accumulation over m ≤ 10⁴ samples)
CORR_TOL = 1e-4
SEED = 0
#: PC levels run at the full DREAM5 width. The depth is cut: at n=1643 the
#: graph after level 1 still has ~143k edges, and level 2 alone took 271 s
#: in `auto` on a v5e (1839 gather-bound chunk dispatches; S-grid similar),
#: so three engines at full depth would not fit a 1200 s smoke. The full
#: depth runs at ORACLE_N.
FULL_N_LEVELS = 1
#: variables of the full-depth stable_ref comparison: the serial oracle
#: needs ~30 s on one x86 CPU core (7 levels, 283 edges)
ORACLE_N = 150


def log(msg: str):
    print(f"[chip_smoke] {msg}", flush=True)


def fail(msg: str):
    print(f"[chip_smoke] FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def same_run(a, b) -> bool:
    return (np.array_equal(a.adj, b.adj)
            and np.array_equal(a.sepsets, b.sepsets)
            and np.array_equal(a.cpdag, b.cpdag))


def mosaic_calls(lowered) -> int:
    return len(_MOSAIC.findall(lowered.compile().as_text()))


def make_samples(ds, n: int, seed: int) -> np.ndarray:
    """The generator's seeded samples, as the f32 the device reads."""
    from repro.data.synthetic_dag import sample_gaussian_dag

    x, _ = sample_gaussian_dag(n=n, m=ds.m, density=ds.density, seed=seed)
    return x.astype(np.float32)


def host_corr(x: np.ndarray) -> np.ndarray:
    """float64 correlation on the host from the f32 samples the chip saw."""
    x = np.asarray(x, np.float32).astype(np.float64)
    xc = x - x.mean(axis=0)
    xn = xc / np.sqrt((xc * xc).mean(axis=0))
    c = xn.T @ xn / x.shape[0]
    np.fill_diagonal(c, 1.0)
    return c


def run_engine(x, alpha, engine, *, max_level=None, orient=True):
    """pc() on the chip, twice: the second call is timed apart from the
    first (which compiles)."""
    from repro.core import pc

    def call():
        return pc(x, alpha=alpha, engine=engine, corr="kernel",
                  max_level=max_level, orient=orient)

    run, first = timed(call)
    again, wall = timed(call)
    if not same_run(again, run):
        fail(f"engine {engine}: two calls on the same data disagree")
    msg = f"wall {wall:.3f}s, first call {first:.3f}s, compile≈{first - wall:.3f}s"
    used = {st["level"]: (st.get("engine"), st.get("dispatches"))
            for st in run.level_stats if not st.get("skipped")}
    spans = {k: round(v, 3) for k, v in again.timings_s.items()}
    log(f"n={x.shape[1]} engine={engine}: {msg}; edges={int(run.adj.sum()) // 2} "
        f"levels={run.levels_run}")
    log(f"n={x.shape[1]} engine={engine}: (engine, dispatches) per level={used}")
    log(f"n={x.shape[1]} engine={engine}: seconds per span of the last call={spans}")
    return run


def agree_with_s(runs, what: str):
    """auto and S-grid must equal the jnp S reference (runs: engine → PCRun)."""
    n = runs["S"].adj.shape[0]
    for e in ("auto", "S-grid"):
        if not same_run(runs[e], runs["S"]):
            diff = int((runs[e].adj != runs["S"].adj).sum()) // 2
            fail(f"engine {e} differs from S at n={n}: {diff} skeleton "
                 "edges differ (or the sepsets or CPDAG do)")
    log(f"auto and S-grid equal S: {what} at n={n}")


def check_mosaic(m, alpha, runs):
    """Recompile each kernel entry point at the shapes a run used (the jit
    caches make this cheap) and require Mosaic custom calls in the HLO.
    runs: (engine name, PCRun) pairs."""
    import jax
    import jax.numpy as jnp

    from repro.core.cit import threshold
    from repro.kernels import ops

    sd = jax.ShapeDtypeStruct
    programs = {"S-kernel": (ops.chunk_s_kernel, "cholinv+cisweep", 2),
                "S-grid": (ops.chunk_s_grid, "sgrid", 1),
                "L1-dense": (None, "level1_dense_kernel", 1)}
    want = {}
    for _, run in runs:
        n = run.adj.shape[0]
        want.setdefault("corr_matmul", (jax.jit(ops.correlation).lower(sd((m, n), jnp.float32)), 1))
        c, adj = sd((n, n), jnp.float32), sd((n, n), jnp.bool_)
        for st in run.level_stats:
            if st.get("skipped") or st.get("engine") not in programs:
                continue
            fn, name, expect = programs[st["engine"]]
            if name in want:
                continue
            tau = threshold(m, st["level"], alpha)
            if fn is None:
                want[name] = (jax.jit(ops.level1_dense).lower(c, adj, tau), expect)
                continue
            sep = sd((n, n, run.sepsets.shape[-1]), jnp.int32)
            if "classes" in st:  # S-kernel at l >= 2: one degree-class program
                k = st["classes"][-1]
                want[name] = (ops.chunk_s_kernel_class.lower(
                    c, adj, sep, sd((n, n), jnp.int32), sd((k["rows_bucket"],), jnp.int32),
                    sd((), jnp.int32), tau, ell=st["level"], n_chunk=k["n_chunk"],
                    n_max=k["bucket"]), expect)
                continue
            ell, n_chunk, npr_b = st["compile_key"]
            want[name] = (fn.lower(
                c, adj, sep, sd((n, npr_b), jnp.int32), sd((n,), jnp.int32),
                sd((), jnp.int32), tau, ell=ell, n_chunk=n_chunk, n_max=npr_b), expect)
    if len(want) != 4:
        fail(f"expected all four Mosaic programs to have run; found {sorted(want)}")
    for name, (lowered, expect) in sorted(want.items()):
        got = mosaic_calls(lowered)
        log(f"mosaic: {name}: {got} tpu_custom_call (want {expect})")
        if got != expect:
            fail(f"{name} did not compile to Mosaic ({got} custom calls)")


def one_chip() -> None:
    import jax.numpy as jnp

    from repro.configs.cupc_datasets import CUPC_DATASETS
    from repro.core.stable_ref import pc_stable_skeleton
    from repro.kernels import ops

    ds = CUPC_DATASETS["DREAM5-Insilico"]
    x = make_samples(ds, ds.n, SEED)
    log(f"{ds.name}: n={ds.n} m={ds.m} density={ds.density} alpha={ds.alpha} "
        f"seed={SEED}; levels ≤ {FULL_N_LEVELS} at this n")

    c_dev = np.asarray(ops.correlation(jnp.asarray(x)), np.float64)
    err = float(np.abs(c_dev - host_corr(x)).max())
    log(f"corr kernel vs float64 host: max |dC| = {err:.3e} (tol {CORR_TOL:g})")
    if not err <= CORR_TOL:
        fail(f"corr kernel error {err:.3e} exceeds {CORR_TOL:g}")

    runs = {e: run_engine(x, ds.alpha, e, max_level=FULL_N_LEVELS, orient=False)
            for e in ("auto", "S-grid", "S")}
    agree_with_s(runs, "skeleton and sepsets")

    # the oracle reads the chip's C (the one pc(corr="kernel") builds), so
    # the comparison isolates the CI tests from f32-vs-f64 rounding of C
    x_o = make_samples(ds, ORACLE_N, SEED)
    c_o = np.asarray(ops.correlation(jnp.asarray(x_o)), np.float64)
    ref, t_ref = timed(lambda: pc_stable_skeleton(c_o, ds.m, alpha=ds.alpha))
    log(f"oracle n={ORACLE_N}: stable_ref {t_ref:.1f}s on the host, "
        f"edges={int(ref.adj.sum()) // 2} levels={ref.max_level}")
    oracle_runs = {e: run_engine(x_o, ds.alpha, e) for e in ("auto", "S-grid", "S")}
    for e, run in oracle_runs.items():
        bad = int((run.adj != ref.adj).sum()) // 2
        log(f"oracle n={ORACLE_N}: engine={e} mismatched edges={bad}")
        if bad:
            fail(f"engine {e} skeleton differs from stable_ref at n={ORACLE_N}")
    agree_with_s(oracle_runs, "skeleton, sepsets and CPDAG")

    check_mosaic(ds.m, ds.alpha, [*runs.items(), *oracle_runs.items()])


def four_chips() -> None:
    import jax
    import jax.numpy as jnp

    from repro.configs.cupc_datasets import CUPC_DATASETS
    from repro.core import make_mesh, pc_from_corr
    from repro.core.distributed import pc_distributed
    from repro.kernels import ops

    if len(jax.devices()) < 4:
        fail(f"--four-chips needs 4 devices, found {len(jax.devices())}")
    ds = CUPC_DATASETS["S.cerevisiae"]
    c = ops.correlation(jnp.asarray(make_samples(ds, ds.n, SEED)))
    log(f"{ds.name}: n={ds.n} m={ds.m} density={ds.density} alpha={ds.alpha} "
        f"seed={SEED}")

    mesh = make_mesh(4)
    sharded, t4 = timed(lambda: pc_distributed(
        c=c, m=ds.m, alpha=ds.alpha, mesh=mesh, engine="S-grid",
        shard_c=True, shard_sep=True))
    log(f"4 devices: first call (includes compile) {t4:.3f}s "
        f"edges={int(sharded.adj.sum()) // 2} levels={sharded.levels_run}")
    single, t1 = timed(lambda: pc_from_corr(c, ds.m, alpha=ds.alpha, engine="S-grid"))
    log(f"1 device:  first call (includes compile) {t1:.3f}s "
        f"edges={int(single.adj.sum()) // 2} levels={single.levels_run}")

    # the C each level read and the sepset rows each level's commit
    # shard_map wrote must sit on four devices, one disjoint row range each
    blocks = {}
    for st in sharded.level_stats:
        if not st.get("skipped"):
            blocks[f"C level {st['level']}"] = st["c_row_blocks"]
            blocks[f"sepsets level {st['level']}"] = st["sep_row_blocks"]
    if not blocks:
        fail("the 4-device run ran no sharded level")
    for name, rows in blocks.items():
        log(f"{name}: (device, first row, end row) {rows}")
        if (len({d for d, _, _ in rows}) != 4
                or len({(a, b) for _, a, b in rows}) != 4):
            fail(f"{name} is not row-sharded over 4 devices")
    if not same_run(sharded, single):
        diff = int((sharded.adj != single.adj).sum()) // 2
        fail(f"4-device result differs from 1 device: {diff} edges (or sepsets)")
    log("4-device S-grid equals the 1-device result: skeleton and sepsets")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the row-sharded path on a 4-chip host")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"[chip_smoke] no TPU: JAX sees {devices[0].platform} devices",
              file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    log(f"device: {devices[0].device_kind} x{len(devices)}")
    if args.four_chips:
        four_chips()
    else:
        one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
