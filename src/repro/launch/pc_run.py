"""Launcher for the paper's workload: PC-stable causal discovery.

    PYTHONPATH=src python -m repro.launch.pc_run --n 500 --m 10000 --d 0.1 \
        --engine auto --alpha 0.01
    PYTHONPATH=src python -m repro.launch.pc_run --dataset DREAM5-Insilico

``--engine`` selects the level engine (see repro/core/engines.py for the
matrix): jnp cuPC-S/-E ("S"/"E"), the Pallas cuPC-S kernel pipeline
("S-kernel"), the grid-resident cuPC-S ("S-grid": the rank loop inside
the Pallas grid, one host dispatch per level — also usable with
--devices, where ``--speculate`` additionally hides the level barrier),
the fused dense ℓ=1 kernel ("L1-dense"), or the production "auto" hybrid
(L1-dense at ℓ=1, S-kernel at ℓ≥2; interpret mode off-TPU).
``--corr`` picks the correlation path (tiled MXU kernel vs XLA einsum).
``--devices K`` runs the row-sharded distributed engine on K (real or
forced-host) devices; level barriers are one OR-all-reduce of the
adjacency per level (DESIGN §4). ``--shard-c`` additionally row-shards
the correlation matrix itself (per-device C memory O(n·k + n²/n_dev)
instead of O(n²) — the >16k-variables regime), with a per-run hot-column
cache (``--no-cache-cols`` restores the per-chunk gather);
``--shard-sep`` row-shards the sepset tensor and commits winners
shard-locally (O(n²·depth/n_dev) per device); ``--pipeline-depth D``
keeps D rank-chunks' CI tests in flight per level (dispatch-ahead,
bit-identical at any depth — docs/ARCHITECTURE.md).

Many-graph modes (repro/batch/):
``--batch B`` learns B independent synthetic datasets in ONE compiled
dispatch (vmapped pc_scan) and reports graphs/sec;
``--bootstrap N`` runs the on-device bootstrap ensemble on the configured
dataset and reports edge frequencies + the stability-selected CPDAG
(``--stability-threshold`` sets the selection cutoff).

Sharding flags (core/sharding.py — all run on forced-host CPU devices
too, see README "Running the sharded paths without a TPU"):
``--mesh K`` builds a flat K-device mesh; ``--shard-batch`` shards the
leading B axis of --batch/--bootstrap over it (same compiled program per
device, B/K local graphs each).

Ranks are int32 and samples f32. A level whose C(n′, ℓ) overflows the int32
commit keys stops with an error; rerun with ``JAX_ENABLE_X64=1 --engine S``
to carry int64 ranks (which the TPU emulates; the Pallas kernels do not
compile for the TPU under x64).
"""
from __future__ import annotations

import argparse
import json

import numpy as np

import jax

from repro.launch.compile_cache import enable_compile_cache
from repro.obs import MonotonicClock

_CLK = MonotonicClock()  # the obs timing seam — no raw perf_counter (RPR003)


def _batch_mesh(args):
    """The mesh for --shard-batch runs (None when sharding is off)."""
    if not args.shard_batch:
        return None
    from repro.core.sharding import make_mesh

    mesh = make_mesh(args.mesh if args.mesh else None)
    print(f"[pc_run] batch axis sharded over {mesh.devices.size} devices")
    return mesh


def _run_bootstrap(args, x, n, m, d, alpha):
    """--bootstrap N: the on-device ensemble on the configured dataset."""
    from repro.batch.ensemble import bootstrap_pc

    mesh = _batch_mesh(args)
    t0 = _CLK.now()
    run = bootstrap_pc(
        x, n_boot=args.bootstrap, alpha=alpha,
        stability_threshold=args.stability_threshold,
        max_level=args.max_level, seed=args.seed, corr=args.corr, mesh=mesh,
    )
    dt = _CLK.now() - t0
    freq = run.edge_freq[np.triu_indices(n, 1)]
    n_stable = len(run.stable_edges())
    print(f"[pc_run] bootstrap N={run.n_boot} threshold={run.stability_threshold}"
          f" widths={run.schedule}")
    print(f"  stable skeleton edges: {n_stable};  mean replicate edges: "
          f"{run.replicate_adj.sum(axis=(1, 2)).mean() / 2:.1f}")
    print(f"  edge-freq deciles (non-zero pairs): "
          f"{np.percentile(freq[freq > 0], [10, 50, 90]).round(2).tolist()}"
          if (freq > 0).any() else "  no edges in any replicate")
    print(f"  directed in aggregated CPDAG: {int((run.cpdag & ~run.cpdag.T).sum())}")
    for k, v in run.timings_s.items():
        print(f"  {k:>16s}: {v*1e3:9.1f} ms")
    print(f"  total: {dt:.2f} s")
    if args.json:
        rec = {
            "mode": "bootstrap", "n": n, "m": m, "density": d,
            "n_boot": run.n_boot, "stability_threshold": run.stability_threshold,
            "stable_edges": n_stable, "timings_s": run.timings_s, "total_s": dt,
        }
        with open(args.json, "w") as f:
            json.dump(rec, f, indent=1)


def _run_batch(args, n, m, d, alpha):
    """--batch B: B independent datasets through one vmapped pc_scan,
    optionally sharded over the mesh (--shard-batch)."""
    from repro.batch.scan_pc import DEFAULT_MAX_LEVEL, plan_schedule
    from repro.core.cit import correlation_from_samples
    from repro.core.engines import batch_run
    from repro.data.synthetic_dag import sample_gaussian_dag

    mesh = _batch_mesh(args)
    cs = np.stack([
        np.asarray(correlation_from_samples(
            sample_gaussian_dag(n=n, m=m, density=d, seed=args.seed + b)[0]))
        for b in range(args.batch)
    ])
    max_level = args.max_level if args.max_level is not None else DEFAULT_MAX_LEVEL
    schedule = plan_schedule(cs, m, alpha=alpha, max_level=max_level, mesh=mesh)
    res = batch_run(cs, m, alpha=alpha, max_level=max_level, n_prime=schedule,
                    mesh=mesh)
    jax.block_until_ready(res.adj)  # compile + first run
    t0 = _CLK.now()
    res = batch_run(cs, m, alpha=alpha, max_level=max_level, n_prime=schedule,
                    mesh=mesh)
    jax.block_until_ready(res.adj)
    dt = _CLK.now() - t0
    edges = np.asarray(res.adj).sum(axis=(1, 2)) // 2
    print(f"[pc_run] batch B={args.batch} max_level={max_level} widths={schedule}")
    print(f"  edges per graph: min={int(edges.min())} mean={edges.mean():.1f} "
          f"max={int(edges.max())};  exact: {int(np.asarray(res.ok).sum())}"
          f"/{args.batch}")
    print(f"  steady-state: {dt:.3f} s -> {args.batch / dt:.1f} graphs/sec")
    if args.json:
        rec = {
            "mode": "batch", "n": n, "m": m, "density": d, "batch": args.batch,
            "schedule": list(schedule), "max_level": max_level,
            "steady_s": dt, "graphs_per_s": args.batch / dt,
        }
        with open(args.json, "w") as f:
            json.dump(rec, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default=None, help="paper Table-1 dataset name")
    ap.add_argument("--n", type=int, default=500)
    ap.add_argument("--m", type=int, default=10_000)
    ap.add_argument("--d", type=float, default=0.1)
    ap.add_argument("--alpha", type=float, default=0.01)
    ap.add_argument(
        "--engine", default="auto",
        choices=["E", "S", "S-kernel", "S-grid", "L1-dense", "auto", "scan"],
        help="level engine: jnp cuPC-E/-S, Pallas cuPC-S pipeline (S-kernel), "
             "grid-resident cuPC-S (S-grid: the rank loop inside the Pallas "
             "grid, one host dispatch per level; also selectable for "
             "--devices runs), fused dense l=1 kernel (L1-dense), the auto "
             "hybrid (L1-dense at l=1 + S-kernel at l>=2; interpret mode "
             "off-TPU), or scan (whole run as one fixed-shape traced "
             "program; static level cap = --max-level, defaulting to the "
             "scan path's DEFAULT_MAX_LEVEL)",
    )
    ap.add_argument(
        "--corr", default="auto", choices=["auto", "kernel", "jnp"],
        help="correlation matrix path: tiled MXU Pallas kernel vs XLA einsum "
             "(auto = kernel on TPU, jnp elsewhere)",
    )
    ap.add_argument(
        "--no-bucket", action="store_true",
        help="disable n'/chunk-shape bucketing (one jit compile per exact "
             "max-degree -- the legacy behaviour; useful for compile probes)",
    )
    ap.add_argument("--max-level", type=int, default=None)
    ap.add_argument("--devices", type=int, default=0, help=">0: distributed over rows")
    ap.add_argument("--mesh", type=int, default=0,
                    help=">0: build a flat K-device mesh (core/sharding.py) "
                         "for the sharded paths; 0 uses all visible devices "
                         "when a sharded flag asks for one. On CPU force "
                         "devices with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=K")
    ap.add_argument("--shard-batch", action="store_true",
                    help="shard the leading B axis of --batch/--bootstrap "
                         "over the mesh (same compiled program per device)")
    ap.add_argument("--shard-c", action="store_true",
                    help="row-shard the correlation matrix in the "
                         "distributed engine (per-device C memory "
                         "O(n*k + n^2/n_dev) instead of O(n^2))")
    ap.add_argument("--shard-sep", action="store_true",
                    help="row-shard the sepset tensor in the distributed "
                         "engine and commit winners shard-locally "
                         "(per-device sepset memory O(n^2*depth/n_dev) "
                         "instead of O(n^2*depth))")
    ap.add_argument("--pipeline-depth", type=int, default=1,
                    help=">=2: keep that many rank-chunks' CI tests in "
                         "flight per level (double-buffered dispatch at 2; "
                         "tests overlap the trailing commits) -- "
                         "bit-identical results at any depth")
    ap.add_argument("--speculate", action="store_true",
                    help="with --devices/--mesh and --engine S-grid: "
                         "dispatch level l+1's first chunk under level l's "
                         "compaction bound BEFORE the max-degree sync "
                         "resolves, hiding the one remaining host "
                         "round-trip per level (bit-identical results)")
    ap.add_argument("--no-cache-cols", action="store_true",
                    help="disable the per-level hot-column cache in "
                         "--shard-c runs (re-gather C[:, cols] inside "
                         "every chunk body -- the legacy traffic pattern)")
    ap.add_argument("--batch", type=int, default=0,
                    help=">0: learn B independent synthetic datasets in one "
                         "vmapped pc_scan dispatch and report graphs/sec")
    ap.add_argument("--bootstrap", type=int, default=0,
                    help=">0: bootstrap-ensemble PC with N on-device "
                         "replicates (repro/batch/ensemble.py)")
    ap.add_argument("--stability-threshold", type=float, default=0.5,
                    help="edge-frequency cutoff for the bootstrap ensemble's "
                         "stability-selected skeleton")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default=None)
    ap.add_argument("--journal", default=None, metavar="PATH",
                    help="enable obs and write the run's trace spans to "
                         "PATH (JSONL; docs/observability.md)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.journal:
        from repro import obs

        obs.configure(enabled=True, journal_path=args.journal)

    from repro.configs.cupc_datasets import CUPC_DATASETS
    from repro.data.synthetic_dag import sample_gaussian_dag

    if args.dataset:
        ds = CUPC_DATASETS[args.dataset]
        n, m, d, alpha = ds.n, ds.m, ds.density, ds.alpha
    else:
        n, m, d, alpha = args.n, args.m, args.d, args.alpha

    print(f"[pc_run] n={n} m={m} density={d} engine=cuPC-{args.engine}"
          + (f" devices={args.devices}" if args.devices else ""))

    if args.batch:  # generates its own B datasets; skip the single-run one
        _run_batch(args, n, m, d, alpha)
        return
    x, _dag = sample_gaussian_dag(n=n, m=m, density=d, seed=args.seed)
    if args.bootstrap:
        _run_bootstrap(args, x, n, m, d, alpha)
        return

    t0 = _CLK.now()
    if args.devices or args.mesh or args.shard_c or args.shard_sep:
        from repro.core.distributed import pc_distributed
        from repro.launch.mesh import make_pc_mesh

        dist_engine = args.engine if args.engine in ("S", "S-grid") else "S"
        if args.engine not in ("auto", "S", "S-grid") or args.corr != "auto":
            print("[pc_run] note: --devices supports --engine S / S-grid "
                  "(sharded cuPC-S); other --engine/--corr selections apply "
                  "to single-device runs only")
        if args.speculate and dist_engine != "S-grid":
            print("[pc_run] warning: --speculate requires --engine S-grid; "
                  "ignoring it for this run")
        mesh = make_pc_mesh(args.devices or args.mesh or None)
        if dist_engine == "S-grid":
            print("[pc_run] grid-resident engine: one fused tests+commit "
                  "launch per level"
                  + (" + speculative next-level dispatch" if args.speculate
                     else ""))
        if args.shard_c:
            print(f"[pc_run] correlation matrix row-sharded over "
                  f"{mesh.devices.size} devices"
                  + (" (hot-column cache off)" if args.no_cache_cols else ""))
        if args.shard_sep:
            print(f"[pc_run] sepset tensor row-sharded over "
                  f"{mesh.devices.size} devices (shard-local commit)")
        if args.pipeline_depth > 1:
            print(f"[pc_run] chunk dispatch pipelined, depth {args.pipeline_depth}")
        run = pc_distributed(x, alpha=alpha, mesh=mesh, max_level=args.max_level,
                             bucket=not args.no_bucket, shard_c=args.shard_c,
                             shard_sep=args.shard_sep,
                             cache_cols=not args.no_cache_cols,
                             pipeline_depth=args.pipeline_depth,
                             engine=dist_engine,
                             speculate=args.speculate and dist_engine == "S-grid")
    else:
        from repro.core.pc import pc

        run = pc(x, alpha=alpha, engine=args.engine, max_level=args.max_level,
                 corr=args.corr, bucket=not args.no_bucket,
                 pipeline_depth=args.pipeline_depth)
    dt = _CLK.now() - t0

    n_edges = int(run.adj.sum()) // 2
    n_directed = int((run.cpdag & ~run.cpdag.T).sum())
    print(f"  levels run: {run.levels_run};  skeleton edges: {n_edges};"
          f"  directed in CPDAG: {n_directed}")
    for k, v in run.timings_s.items():
        print(f"  {k:>8s}: {v*1e3:9.1f} ms")
    print(f"  total: {dt:.2f} s")

    if args.json:
        rec = {
            "n": n, "m": m, "density": d, "engine": args.engine,
            "edges": n_edges, "levels": run.levels_run,
            "timings_s": run.timings_s, "total_s": dt,
        }
        with open(args.json, "w") as f:
            json.dump(rec, f, indent=1)


if __name__ == "__main__":
    main()
