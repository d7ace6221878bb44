"""The per-layer readers of the program's named spans and host-sync count,
on hand-made runs and traces: what they read, and None where the program
has no such span or count (as before the spans existed)."""
from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import registry  # noqa: E402
from bench.trace_view import TraceView  # noqa: E402

READERS = ("entry_s", "readback_s", "host_sync_wait_s", "host_syncs_per_graph",
           "chunk_dispatch_s", "idle_unattributed_share")
MS = 1_000_000


def reader(name):
    return registry.metric_reader(name)


def graph(timings, counts=None):
    out = SimpleNamespace(timings_s=timings)
    if counts is not None:
        out.counts = counts
    return SimpleNamespace(output=out)


def a_run(graphs, trace=None, window=(0, 100 * MS)):
    return SimpleNamespace(graphs=graphs, trace=trace, window=window)


def test_readers_are_registered_for_the_cells_they_list():
    bm = registry.load_benchmark()
    per_layer = {m["name"]: m for m in bm["per_layer"]}
    for name in READERS:
        assert per_layer[name]["moves"] == "graph_s"
        assert callable(reader(name))
    assert per_layer["chunk_dispatch_s"]["workloads"] == ["nci60.full"]
    for name in set(READERS) - {"chunk_dispatch_s"}:
        assert per_layer[name]["workloads"] == ["nci60.full", "dream5.order1", "nci60.order1"]


def test_span_readers_average_over_graphs():
    run = a_run([
        graph({"upload": 0.01, "validate": 0.02, "corr": 0.03, "readback": 0.1,
               "sync": 0.5, "chunk": 0.2, "total": 1.0}, {"host_syncs": 17}),
        graph({"upload": 0.03, "validate": 0.04, "corr": 0.05, "readback": 0.3,
               "sync": 0.7, "chunk": 0.4, "total": 1.2}, {"host_syncs": 19}),
        SimpleNamespace(output=None),  # a graph that raised: not counted
    ])
    assert reader("entry_s")(run) == pytest.approx(0.09)
    assert reader("readback_s")(run) == pytest.approx(0.2)
    assert reader("host_sync_wait_s")(run) == pytest.approx(0.6)
    assert reader("chunk_dispatch_s")(run) == pytest.approx(0.3)
    assert reader("host_syncs_per_graph")(run) == pytest.approx(18.0)


def test_span_readers_read_nothing_without_the_spans():
    """A program without the spans and the count (the layout before them):
    every reader gives None and none raises."""
    before = a_run([graph({"level0": 0.02, "level1": 0.4, "orient": 0.01, "total": 0.5})])
    for name in READERS:
        assert reader(name)(before) is None
    # an order-1 run has every span but no chunk program
    order1 = a_run([graph({"upload": 0.1, "readback": 0.01, "sync": 0.2, "total": 0.5},
                          {"host_syncs": 9})])
    assert reader("chunk_dispatch_s")(order1) is None
    assert reader("host_syncs_per_graph")(order1) == 9


def test_idle_unattributed_share_by_hand():
    # device busy [10, 20), [50, 60) and [95, 100) in a [0, 100) window;
    # total/ children cover [5, 30) and [40, 70)
    view = TraceView(
        devices={"/device:TPU:0": [("a", 10 * MS, 20 * MS), ("b", 50 * MS, 60 * MS),
                                   ("late", 95 * MS, 130 * MS)]},
        host=[("bench_window", 0, 100 * MS), ("graph", 0, 90 * MS),
              ("total", 2 * MS, 88 * MS), ("total/level0", 5 * MS, 30 * MS),
              ("total/level0/sync", 25 * MS, 30 * MS), ("total/level1", 40 * MS, 70 * MS)])
    # idle: [0, 10) [20, 50) [60, 95) = 75 ms; named: 5 + 10 + 10 + 10 = 35 ms
    assert reader("idle_unattributed_share")(a_run([], view)) == pytest.approx(
        100.0 * 40 / 75)
    # a plane that runs nothing (the v5e's Megascale plane) is no device
    view.devices["/device:CUSTOM:Megascale Trace"] = []
    assert reader("idle_unattributed_share")(a_run([], view)) == pytest.approx(
        100.0 * 40 / 75)
    # the same window with the children covering every idle stretch
    view.host.append(("total/readback", 0, 100 * MS))
    assert reader("idle_unattributed_share")(a_run([], view)) == pytest.approx(0.0)


def test_idle_unattributed_share_reads_nothing_without_annotations():
    bare = TraceView(devices={"/device:TPU:0": [("a", 10 * MS, 20 * MS)]},
                     host=[("bench_window", 0, 100 * MS), ("graph", 0, 90 * MS),
                           ("total", 1 * MS, 89 * MS)])
    assert reader("idle_unattributed_share")(a_run([], bare)) is None
    assert reader("idle_unattributed_share")(a_run([], TraceView())) is None
    assert reader("idle_unattributed_share")(a_run([], None)) is None
    # no idle time in the window: nothing to share out
    busy = TraceView(devices={"/device:TPU:0": [("a", 0, 100 * MS)]},
                     host=[("total/level1", 0, 100 * MS)])
    assert reader("idle_unattributed_share")(a_run([], busy)) is None


@pytest.mark.parametrize("name", [m["name"] for m in registry.load_benchmark()["per_layer"]])
def test_reader_of_another_calls_output_reads_nothing(name):
    """Outputs of another entry point keep no spans, counts, level records
    or skeleton, and the trace holds none of the program's annotations:
    every reader gives None and none raises, except the device's idle share,
    which reads the trace alone."""
    view = TraceView(devices={"/device:TPU:0": [("op", 10 * MS, 40 * MS)]},
                     host=[("bench_window", 0, 100 * MS), ("graph", 0, 90 * MS)])
    run = a_run([SimpleNamespace(output=SimpleNamespace(total=[1, 2])),
                 SimpleNamespace(output=None)], view)
    expect = pytest.approx(70.0) if name == "device_idle_share" else None
    assert reader(name)(run) == expect
