#!/usr/bin/env python3
"""Whole runs of a cell with the program replaced: the readings behind each
limit in bench/limits/.

    python3 bench/control.py --workload nci60.full --as program control flip --seeds 1 2 3

For every role and seed, in one process, this makes the run
``bench/run.py --workload <cell> --seed <seed> --seconds 0 --trace 0``
makes (set-up, a window of one pass over the timed datasets, the fresh
datasets, the reference comparison and the cell's limits), with the call
the window times replaced:

    program  the program itself: lower readings on more seeds
    control  the reference one precision step down: the configuration
             states float32 with matmuls at precision ``highest``, so the
             control computes the correlation with the device's matmul at
             ``high`` (three bfloat16 passes on a TPU) and every partial
             correlation in float32; ``correct`` has to read false
    flip     the program with one kept edge of each output reported
             removed at level 0 (an answer altered where it is produced);
             ``correct`` has to read false

Each run prints a header line and then its result line on standard
output. The benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import hashlib
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run  # noqa: E402
from bench.reference import orient, pc_stable  # noqa: E402


def control_output(x, config: dict, mix: dict):
    """The reference at one precision step below the configuration's, in
    the layout of the program's output."""
    import jax
    import jax.numpy as jnp

    xs = jnp.asarray(x, jnp.float32)
    xs = xs - xs.mean(axis=0)
    xs = xs / jnp.sqrt((xs * xs).sum(axis=0))
    c = np.array(jnp.matmul(xs.T, xs, precision=jax.lax.Precision.HIGH))
    np.fill_diagonal(c, 1.0)
    adj, sep = pc_stable.pc_stable(c, x.shape[0], config["alpha"], max_level=mix["max_level"],
                                   sepset_depth=mix["sepset_depth"], screen=True)
    cpdag = orient.cpdag(adj, sep) if mix["orient"] else adj
    return SimpleNamespace(adj=adj, sepsets=sep, cpdag=cpdag)


_CONTROL_OUTPUTS = {}  # (cell, dataset digest) -> output: the control is deterministic


def control(cell, seed: int):
    def call(x):
        key = (cell.name, hashlib.sha1(np.ascontiguousarray(x).tobytes()).digest())
        if key not in _CONTROL_OUTPUTS:
            _CONTROL_OUTPUTS[key] = control_output(x, cell.config, cell.traffic)
        return _CONTROL_OUTPUTS[key]

    return call


def flip_edge(out, seed: int):
    """One kept edge, drawn from the seed, reported as removed at level 0."""
    adj, sep, cp = (np.array(out.adj), np.array(out.sepsets), np.array(out.cpdag))
    edges = np.argwhere(np.triu(adj, 1))
    i, j = edges[np.random.default_rng(seed).integers(len(edges))]
    adj[i, j] = adj[j, i] = cp[i, j] = cp[j, i] = False
    sep[i, j, 0] = sep[j, i, 0] = -2
    return SimpleNamespace(adj=adj, sepsets=sep, cpdag=cp)


def flip(cell, seed: int):
    call = run.program(cell, seed)
    return lambda x: flip_edge(call(x), seed)


ROLES = {"program": run.program, "control": control, "flip": flip}


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
                          make_call=ROLES[role], started=time.monotonic())
            sys.stdout.flush()
            if rc:
                return rc
    return 0


if __name__ == "__main__":
    sys.exit(main())
