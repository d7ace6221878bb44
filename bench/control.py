#!/usr/bin/env python3
"""Whole runs of a cell with the program replaced: the readings behind each
limit in bench/limits/.

    python3 bench/control.py --workload nci60.full --as program control flip --seeds 1 2 3

For every role and seed, in one process, this makes the run
``bench/run.py --workload <cell> --seed <seed> --seconds 0 --trace 0``
makes (set-up, a window of one pass over the timed datasets, the fresh
datasets, the reference comparison and the cell's limits), with the call
the window times replaced by what the cell's call module
(bench/calls/<call>.py) gives for the role:

    program  ``make``: the program itself, for lower readings on more seeds
    control  ``control``: the plain reference one precision step below
             the configuration's (for ``pc``: the correlation's matmul at
             ``high``, three bfloat16 passes on a TPU, and every partial
             correlation in float32); ``correct`` has to read false
    flip     ``flip``: the program with one answer altered where it is
             produced (for ``pc``: one kept edge of each output reported
             removed at level 0); ``correct`` has to read false

Each run prints a header line and then its result line on standard
output. The benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run  # noqa: E402

#: each role, as the name of what the cell's call module gives for it
ROLES = {"program": "make", "control": "control", "flip": "flip"}


def role_call(role: str):
    """``make_call(cell, seed)`` for run.main: the role as the cell's call
    module (bench/calls/<call>.py) makes it."""
    return lambda cell, seed: getattr(cell.call, ROLES[role])(cell, seed)


def main(argv=None, *, root: Path = ROOT, need_chip: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--as", dest="roles", nargs="+", choices=sorted(ROLES), required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    a = ap.parse_args(argv)
    for role in a.roles:
        for seed in a.seeds:
            print(f"control.py: {a.workload} as {role}, seed {seed}", flush=True)
            rc = run.main(["--workload", a.workload, "--seed", str(seed), "--seconds", "0",
                           "--trace", "0"], root=root, need_chip=need_chip,
                          make_call=role_call(role), started=time.monotonic())
            sys.stdout.flush()
            if rc:
                return rc
    return 0


if __name__ == "__main__":
    sys.exit(main())
