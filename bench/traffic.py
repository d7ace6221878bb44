"""The one traffic generator: turns a configuration and a traffic mix (both
data files) and a seed into the datasets of a run and the order in which
the closed loop submits them.

Every mix is timed in a closed loop: one client, one graph in flight, the
next graph submitted when the last one has returned (a researcher who runs
a job and waits for it). A traffic mix (bench/traffic/<mix>.json) sets:

    pool        the timed datasets, made in set-up; every pass of the
                window submits each of them once, in a fresh seeded order
    max_level   deepest PC level run (null: until no row has enough
                neighbours or the sepset depth is reached)
    sepset_depth
                slots per separating set in the output: the largest set a
                run can record
    orient      orient the skeleton into a CPDAG
    check_pool  timed datasets, drawn from the seed, whose output is held
                to the reference
    fresh       datasets drawn from the seed, run through the same call
                after the window and held to the reference

A configuration (bench/configs/<config>.json) names its data law
(bench/data/<law>.py, a ``sample(n, m, density, seed, network)``
function), its sizes, and ``data_seed``: the network and the timed
datasets come from it, so that every run times the same work, and the
seed changes the order, the datasets checked and the fresh datasets.

A mix with any other key is refused: this generator would not act on it.
"""
from __future__ import annotations

import numpy as np

from bench import registry

#: the keys a mix may set; ``name`` and ``why`` describe it
KEYS = {"name", "why", "pool", "max_level", "sepset_depth", "orient", "check_pool", "fresh"}


def law(config: dict, root=registry.ROOT):
    return registry.load_module(root / "bench" / "data" / f"{config['law']}.py",
                                f"bench_law_{config['law']}")


def _draw(config: dict, seed, root) -> np.ndarray:
    return law(config, root).sample(
        config["n"], config["m"], config["density"], seed=seed,
        network=config["data_seed"]).astype(np.float32)


def make_pool(config: dict, traffic: dict, root=registry.ROOT) -> list:
    """The timed datasets: (m, n) float32 sample matrices, the k-th drawn
    from the seed sequence (data_seed, k)."""
    unknown = set(traffic) - KEYS
    if unknown:
        raise KeyError(f"bench/traffic.py acts on no mix key {sorted(unknown)}")
    return [_draw(config, [config["data_seed"], k], root)
            for k in range(int(traffic["pool"]))]


def make_fresh(config: dict, traffic: dict, seed: int, root=registry.ROOT) -> list:
    """The run's fresh datasets, the k-th drawn from (seed, k)."""
    return [_draw(config, [seed, k], root) for k in range(int(traffic["fresh"]))]


def pass_order(traffic: dict, seed: int, p: int) -> list:
    """Dataset indices of the p-th pass over the pool."""
    rng = np.random.default_rng([seed, int(traffic["pool"]), p])
    return [int(k) for k in rng.permutation(int(traffic["pool"]))]


def checked(traffic: dict, seed: int) -> list:
    """The pool datasets, drawn from the seed, whose output the reference
    checks."""
    k = int(traffic["pool"])
    rng = np.random.default_rng([seed, k, 0xC4EC])  # a stream apart from the passes
    return sorted(int(i) for i in rng.choice(k, size=min(int(traffic["check_pool"]), k),
                                              replace=False))


def pc_options(config: dict, traffic: dict) -> dict:
    """The keyword arguments of the program's ``pc`` call."""
    return {"alpha": config["alpha"], "max_level": traffic["max_level"],
            "sepset_depth": traffic["sepset_depth"], "orient": traffic["orient"]}
