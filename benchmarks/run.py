"""Benchmark aggregator — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # scaled (CPU, minutes)
    PYTHONPATH=src python -m benchmarks.run --quick    # smoke subset
    PYTHONPATH=src python -m benchmarks.run --full     # paper-scale n (hours)

Writes benchmarks/results/*.json + benchmarks/results/REPORT.md. Every
module runs in this one process (one process per chip); the exit status is
1 when any module failed.
"""
from __future__ import annotations

import argparse
import sys
import time

from repro.launch.compile_cache import enable_compile_cache

from . import (fig5, fig6, fig7_8, fig9, fig10, pc_batch, pc_cit,
               pc_distributed, pc_engines, pc_grid, pc_hillclimb, pc_serve,
               roofline_table, table2)
from .common import RESULTS

MODULES = [
    ("table2", table2),
    ("fig5", fig5),
    ("fig6", fig6),
    ("fig7_8", fig7_8),
    ("fig9", fig9),
    ("fig10", fig10),
    ("pc_engines", pc_engines),
    ("pc_batch", pc_batch),
    ("pc_distributed", pc_distributed),
    ("pc_grid", pc_grid),
    ("pc_cit", pc_cit),
    ("pc_serve", pc_serve),
    ("pc_hillclimb", pc_hillclimb),
    ("roofline", roofline_table),
]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()

    sections = []
    failed = []
    for name, mod in MODULES:
        if args.only and args.only != name:
            continue
        t0 = time.perf_counter()
        try:
            md = mod.run(full=args.full, quick=args.quick)
            dt = time.perf_counter() - t0
            print(f"[bench] {name:10s} ok in {dt:6.1f}s", flush=True)
            sections.append(md)
        except Exception as e:  # finish the report, then exit non-zero
            print(f"[bench] {name:10s} FAILED: {e!r}", flush=True)
            sections.append(f"### {name} — FAILED: {e!r}")
            failed.append(name)
    RESULTS.mkdir(parents=True, exist_ok=True)
    report = "# Benchmark report (paper tables/figures analogues)\n\n" + "\n\n".join(sections) + "\n"
    (RESULTS / "REPORT.md").write_text(report)
    print(f"[bench] report -> {RESULTS / 'REPORT.md'}")
    print(report)
    if failed:
        print(f"[bench] FAILED modules: {', '.join(failed)}", flush=True)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
