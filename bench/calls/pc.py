"""The call ``repro.core.pc``: PC-stable from a sample matrix, as a user
calls it with the cell's options. The call of every configuration that
names no ``call``.

Mix keys it acts on (besides the generator's, bench/traffic.py):

    max_level   deepest PC level run (null: until no row has enough
                neighbours or the sepset depth is reached)
    sepset_depth
                slots per separating set in the output: the largest set a
                run can record
    orient      orient the skeleton into a CPDAG

Besides ``make``, the call the window times, it gives bench/control.py
its two other roles: ``control``, the reference one precision step below
the configuration's, and ``flip``, the program with one answer altered.
"""
from __future__ import annotations

import hashlib
from types import SimpleNamespace

import numpy as np

KEYS = {"max_level", "sepset_depth", "orient"}


def options(config: dict, traffic: dict) -> dict:
    """The keyword arguments of the program's ``pc`` call."""
    return {"alpha": config["alpha"], "max_level": traffic["max_level"],
            "sepset_depth": traffic["sepset_depth"], "orient": traffic["orient"]}


def make(cell, seed: int):
    """The system under test: the call the window times."""
    from repro.core import pc

    kw = options(cell.config, cell.traffic)

    def call(x):
        return pc(x, engine="auto", corr="kernel", **kw)

    return call


def same(a, b) -> bool:
    """Two outputs on one dataset are identical: skeleton, sepsets, CPDAG."""
    return all((getattr(a, f) == getattr(b, f)).all() for f in ("adj", "sepsets", "cpdag"))


def control_output(x, config: dict, mix: dict):
    """The reference at one precision step below the configuration's, in
    the layout of the program's output: the configuration states float32
    with matmuls at precision ``highest``, so the correlation takes the
    device's matmul at ``high`` (three bfloat16 passes on a TPU) and every
    partial correlation float32."""
    import jax
    import jax.numpy as jnp

    from bench.reference import orient, pc_stable

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
    call = make(cell, seed)
    return lambda x: flip_edge(call(x), seed)
