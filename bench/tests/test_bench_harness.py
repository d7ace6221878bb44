"""The benchmark harness on the CPU: discovery by name, trace reduction,
the end-to-end arithmetic, and whole runs (chip look skipped) that must
come out correct on the sound program and not correct on broken ones."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import registry, run, traffic  # noqa: E402
from bench.trace_view import TraceView, union  # noqa: E402

TINY = {"name": "tiny", "n": 24, "m": 300, "density": 0.2, "alpha": 0.01,
        "law": "gaussian_dag", "reference": "pc_stable", "data_seed": 24}
MIX = {"name": "tiny_mix", "pool": 2, "max_level": None, "sepset_depth": 8, "orient": True,
       "check_pool": 2, "fresh": 1}
LIMITS = {"z_gap": 0.01, "bad": 0, "cpdag_diff": 0, "fresh_z_gap": None, "fresh_bad": 0,
          "fresh_cpdag_diff": 0, "repeat_diff": 0, "failed": 0}


def make_root(tmp_path: Path) -> Path:
    """A checkout holding the benchmark's code and one more configuration,
    traffic mix, per-layer metric and cell, each added only as files and
    entries."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(TINY))
    (root / "bench" / "traffic" / "tiny_mix.json").write_text(json.dumps(MIX))
    (root / "bench" / "limits" / "tiny.tiny_mix.json").write_text(json.dumps(LIMITS))
    (root / "bench" / "metrics" / "graphs_seen.py").write_text(
        "def read(run):\n    return len(run.graphs)\n")
    bm["configs"].append({"name": "tiny", "source": "test", "file": "bench/configs/tiny.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": "tiny.tiny_mix", "config": "tiny",
                            "traffic": "tiny_mix", "chips": 1, "why": "test"})
    bm["per_layer"].append({"name": "graphs_seen", "unit": "graphs", "better": "higher",
                            "source": "program_counter", "layer": "test",
                            "moves": "graph_s", "workloads": ["tiny.tiny_mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return root


def test_new_files_are_found_by_name(tmp_path):
    root = make_root(tmp_path)
    cell = registry.load_cell("tiny.tiny_mix", root)
    assert cell.config == TINY and cell.traffic == MIX and cell.limits == LIMITS
    assert [m["name"] for m in cell.per_layer if m["name"] == "graphs_seen"] == ["graphs_seen"]
    assert "graph_p95_s" not in [m["name"] for m in cell.end_to_end]
    assert registry.metric_reader("graphs_seen", root)(type("R", (), {"graphs": [1, 2]})) == 2
    pool = traffic.make_pool(cell.config, cell.traffic, root)
    assert len(pool) == 2 and pool[0].shape == (300, 24) and pool[0].dtype == np.float32
    fresh = traffic.make_fresh(cell.config, cell.traffic, 2**31 + 5, root)
    again = traffic.make_fresh(cell.config, cell.traffic, 2**31 + 5, root)
    assert len(fresh) == 1 and (fresh[0] == again[0]).all()
    assert not (fresh[0] == traffic.make_fresh(cell.config, cell.traffic, 6, root)[0]).all()
    assert sorted(traffic.pass_order(cell.traffic, 7, 3)) == [0, 1]


def test_committed_cells_load():
    for w in registry.load_benchmark()["workloads"]:
        cell = registry.load_cell(w["name"])
        for m in cell.per_layer:
            assert callable(registry.metric_reader(m["name"]))
        assert {"repeat_diff", "failed", "z_gap", "bad", "fresh_bad"} <= set(cell.limits)


def test_trace_reduction_busy_and_idle():
    ms = 1_000_000
    view = TraceView(
        devices={"/device:TPU:0": [("corr", 0, 10 * ms), ("l1", 5 * ms, 20 * ms),
                                   ("l2", 40 * ms, 70 * ms), ("late", 95 * ms, 120 * ms)]},
        host=[("bench_window", 0, 100 * ms), ("graph", 0, 90 * ms), ("total", 1 * ms, 89 * ms),
              ("total/level1", 2 * ms, 4 * ms), ("total/level2", 32 * ms, 35 * ms)],
        runtime=[("Transpose::Execute", 72 * ms, 80 * ms)])
    assert union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4), (5, 10)]
    lo, hi = view.spans("bench_window")[0]
    assert view.busy_ns([(lo, hi)]) == 55 * ms  # [0, 20) + [40, 70) + [95, 100)
    # level 1's annotation ends at dispatch; its phase runs to level 2's start
    assert view.spans("level1") == [(2 * ms, 4 * ms)]
    assert view.phases("level1") == [(2 * ms, 32 * ms)]
    assert view.busy_ns(view.phases("level1")) == 18 * ms  # [2, 20)
    assert view.phases("level2") == [(32 * ms, 90 * ms)]  # to the graph's end
    gaps = view.idle_gaps(lo, hi)
    assert [(g[0], round(g[1], 6)) for g in gaps] == [
        ("total | Transpose::Execute", 0.025), ("total", 0.02)]
    top = view.top_ops(lo, hi)
    assert top[0] == ["l2", pytest.approx(0.03)]


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_graph_s_and_p95_by_hand():
    clock = FakeClock()
    durations = {0: 1.0, 1: 3.0}

    def call(k):
        clock.t += durations[k]
        return k

    mix = {"pool": 2}
    graphs, window_s = run.run_window(call, [0, 1], mix, seed=11, seconds=5.0, clock=clock)
    # passes of 4 s start at t = 0 and 4; the second ends at 8 > 5 and is let finish
    assert len(graphs) == 4 and window_s == pytest.approx(8.0)
    assert run.end_to_end("graph_s", graphs, window_s, 0.0) == pytest.approx(2.0)
    lat = sorted(g.latency_s for g in graphs)
    assert lat == [1.0, 1.0, 3.0, 3.0]
    assert run.end_to_end("graph_p95_s", graphs, window_s, 0.0) == pytest.approx(3.0)
    assert run.end_to_end("setup_s", graphs, window_s, 12.5) == 12.5


@pytest.fixture
def fast_pc(monkeypatch):
    """The program's pc on the jnp engine: the harness, not the kernels,
    is under test here."""
    from repro import core

    real = core.pc

    def pc(x, **kw):
        return real(x, **{**kw, "engine": "S", "corr": "jnp"})

    monkeypatch.setattr(core, "pc", pc)
    return pc


def one_run(tmp_path, capsys, trace=0):
    root = make_root(tmp_path)
    rc = run.main(["--workload", "tiny.tiny_mix", "--seed", "4242424242", "--seconds", "0",
                   "--trace", str(trace)], root=root, need_chip=False)
    out = capsys.readouterr().out.strip().splitlines()[-1]
    return rc, json.loads(out)


def test_sound_run_is_correct(tmp_path, capsys, fast_pc):
    rc, res = one_run(tmp_path, capsys)
    assert rc == 0 and res["correct"] is True
    assert list(res)[-1] == "checks" and res["checks"]["z_gap"]["limit"] == LIMITS["z_gap"]
    assert "fresh_z_gap" in res["reported"] and "fresh_z_gap" not in res["checks"]
    assert set(res["metrics"]) == {"graph_s", "setup_s"}
    assert res["attempted"] == 2 and res["failed"] == 0


def test_traced_run_reads_per_layer_metrics(tmp_path, capsys, fast_pc):
    rc, res = one_run(tmp_path, capsys, trace=1)
    assert rc == 0 and res["correct"] is True
    assert res["metrics"]["graphs_seen"]["value"] == 2
    assert res["metrics"]["level1_s"]["value"] > 0
    # the CPU backend writes no device plane, so nothing reads as busy
    assert res["device"]["busy_s"] == 0 and res["device"]["window_s"] > 0
    assert "device_ops" in res["breakdown"]


def _broken(monkeypatch, fault):
    from repro import core

    real = core.pc

    def pc(x, **kw):
        out = real(x, **{**kw, "engine": "S", "corr": "jnp"})
        return fault(out)

    monkeypatch.setattr(core, "pc", pc)


def _flip_one_edge(out):
    """An answer altered where it is produced: one kept edge dropped."""
    for f in ("adj", "sepsets", "cpdag"):
        setattr(out, f, np.array(getattr(out, f)))
    i, j = np.argwhere(np.triu(out.adj, 1))[0]
    out.adj[i, j] = out.adj[j, i] = False
    out.sepsets[i, j, 0] = out.sepsets[j, i, 0] = -2
    out.cpdag[i, j] = out.cpdag[j, i] = False
    return out


def test_altered_answer_is_not_correct(tmp_path, capsys, monkeypatch):
    _broken(monkeypatch, _flip_one_edge)
    rc, res = one_run(tmp_path, capsys)
    assert rc == 0 and res["correct"] is False
    assert res["checks"]["z_gap"]["value"] > LIMITS["z_gap"]


def test_level_returning_its_state_unchanged_is_not_correct(tmp_path, capsys, monkeypatch):
    """A step that returns its state unchanged: level 1 removes nothing."""
    from repro.core import engines

    real = engines.run_level

    def run_level(c, adj, sep, ell, tau, **kw):
        new = real(c, adj, sep, ell, tau, **kw)
        return (adj, sep, new[2]) if ell == 1 else new

    monkeypatch.setattr(engines, "run_level", run_level)
    _broken(monkeypatch, lambda out: out)
    rc, res = one_run(tmp_path, capsys)
    assert res["correct"] is False
    assert res["checks"]["z_gap"]["value"] > LIMITS["z_gap"]


def test_half_the_rows_left_out_is_not_correct(tmp_path, capsys, monkeypatch):
    """Half of the work left out: level 1 commits only the removals whose
    endpoints both lie in the first half of the rows."""
    import jax.numpy as jnp

    from repro.core import engines

    real = engines.run_level

    def run_level(c, adj, sep, ell, tau, **kw):
        adj2, sep2, st = real(c, adj, sep, ell, tau, **kw)
        if ell != 1:
            return adj2, sep2, st
        half = jnp.arange(adj.shape[0]) < adj.shape[0] // 2
        keep_old = ~(half[:, None] & half[None, :])
        return (jnp.where(keep_old, adj, adj2),
                jnp.where(keep_old[..., None], sep, sep2), st)

    monkeypatch.setattr(engines, "run_level", run_level)
    _broken(monkeypatch, lambda out: out)
    rc, res = one_run(tmp_path, capsys)
    assert res["correct"] is False


def test_no_chip_exits_without_result(capsys):
    rc = run.main(["--workload", "nci60.full", "--seed", "1", "--seconds", "1"])
    assert rc == 2
    assert capsys.readouterr().out == ""


def test_mix_key_the_generator_ignores_is_refused(tmp_path):
    root = make_root(tmp_path)
    cell = registry.load_cell("tiny.tiny_mix", root)
    with pytest.raises(KeyError, match="in_flight"):
        traffic.make_pool(cell.config, {**cell.traffic, "in_flight": 2}, root)
