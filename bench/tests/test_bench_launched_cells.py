"""The reader of ``level2up_launched_cells`` on hand-made runs: a level run
as degree classes, a skipped level, and a level with no ``launched_tests``
(a level-wide chunk plan), which reads None."""
from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import registry  # noqa: E402


def graph(*levels):
    return SimpleNamespace(output=SimpleNamespace(level_stats=list(levels)))


def read(run):
    return registry.metric_reader("level2up_launched_cells")(run)


def test_registered_for_nci60_full():
    per_layer = {m["name"]: m for m in registry.load_benchmark()["per_layer"]}
    m = per_layer["level2up_launched_cells"]
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (
        "cells/graph", "lower", "program_counter", "planner", "graph_s")
    assert m["workloads"] == ["nci60.full"]


def test_sums_levels_two_and_up_per_graph():
    level1 = {"level": 1, "engine": "L1-dense", "chunks": 1}
    classed = {"level": 2, "engine": "S-kernel", "chunks": 3, "launched_tests": 1_000,
               "classes": [{"bucket": 8, "rows": 5, "rows_bucket": 8, "n_chunk": 32,
                            "chunks": 1}]}
    skipped = {"level": 3, "skipped": True, "chunks": 0}
    run = SimpleNamespace(graphs=[
        graph(level1, classed, {"level": 3, "launched_tests": 24}),
        graph(level1, classed, skipped),
        SimpleNamespace(output=None),  # a graph that raised: not counted
    ])
    assert read(run) == pytest.approx((1_024 + 1_000) / 2)


def test_reads_nothing_without_the_count():
    """A level-wide chunk plan keeps n_chunk and npr_bucket, not
    launched_tests: the reader gives None and does not raise."""
    plain = {"level": 2, "engine": "S-kernel", "chunks": 26, "n_chunk": 32,
             "npr_bucket": 64}
    assert read(SimpleNamespace(graphs=[graph({"level": 1}, plain)])) is None
    # an order-1 run: nothing at l >= 2 to count, and no graph at all
    assert read(SimpleNamespace(graphs=[graph({"level": 1})])) == 0
    assert read(SimpleNamespace(graphs=[])) is None
