"""Counts of PC-stable's work, as the per-layer metrics compare them.

A test is one (row i, conditioning set S, neighbour j): the partial
correlation of i and j given S. PC-stable needs, at level l, every
l-subset S of adj(i) minus j for every ordered edge (i, j) of the graph the
level starts from: sum_i d_i * C(d_i - 1, l). A level-wide chunk plan
launches n rows x (chunks x ranks per chunk) sets x n' neighbour slots,
whatever the rows' own degrees; degree classes launch, per class, its
padded rows x its chunks x ranks per chunk x its own n' bucket, which the
program sums into the level's ``launched_tests``.
"""
from __future__ import annotations

import math

import numpy as np

from bench.reference.pc_stable import removal_level


def starting_degrees(output, ell: int) -> np.ndarray:
    """Row degrees of the graph level l started from, rebuilt from an
    output's skeleton and sepsets."""
    adj = np.asarray(output.adj, bool)
    lvl = removal_level(adj, np.asarray(output.sepsets))
    g = ((lvl == -1) | (lvl >= ell)) & ~np.eye(adj.shape[0], dtype=bool)
    return g.sum(axis=1)


def needed_tests(output, ell: int) -> int:
    return sum(int(d) * math.comb(int(d) - 1, ell)
               for d in starting_degrees(output, ell) if d > ell)


def launched_tests(n: int, stats: dict) -> int | None:
    """Tests a chunked level launched, from its level_stats entry: the
    degree classes' ``launched_tests`` where the level records it, else its
    level-wide plan's n x chunks x n_chunk x npr_bucket; None when the
    entry says neither (a level run by no chunk program)."""
    if stats.get("skipped"):
        return 0
    if "launched_tests" in stats:
        return int(stats["launched_tests"])
    if not all(k in stats for k in ("chunks", "n_chunk", "npr_bucket")):
        return None
    return n * int(stats["chunks"]) * int(stats["n_chunk"]) * int(stats["npr_bucket"])
