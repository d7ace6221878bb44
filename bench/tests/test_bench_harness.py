"""The benchmark harness on the CPU: discovery by name, trace reduction,
the end-to-end arithmetic, and whole runs (chip look skipped) that must
come out correct on the sound program and not correct on broken ones; a
stand-in entry point, added as files only, runs through the same harness."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import registry, run, traffic  # noqa: E402
from bench.trace_view import TraceView, clipped, union  # noqa: E402

TINY = {"name": "tiny", "n": 24, "m": 300, "density": 0.2, "alpha": 0.01,
        "law": "gaussian_dag", "reference": "pc_stable", "data_seed": 24}
MIX = {"name": "tiny_mix", "pool": 2, "max_level": None, "sepset_depth": 8, "orient": True,
       "check_pool": 2, "fresh": 1}
LIMITS = {"z_gap": 0.01, "bad": 0, "cpdag_diff": 0, "fresh_z_gap": None, "fresh_bad": 0,
          "fresh_cpdag_diff": 0, "repeat_diff": 0, "failed": 0}

# A stand-in entry point: categorical codes in, per-column sums out (an
# output with no adj, sepsets, spans or level records), its own mix key
# ``scale``, its own repeat check, control, planted fault, data law and
# reference. Its control sums in int8, which a column of 100 codes at scale
# 2 overflows.
CODES = {"name": "codes", "n": 5, "m": 100, "density": 0.0, "law": "codes",
         "reference": "colsum", "call": "colsum", "dtype": "int32", "data_seed": 3}
CODES_MIX = {"name": "codes_mix", "pool": 3, "check_pool": 2, "fresh": 1, "scale": 2}
CODES_LIMITS = {"total_diff": 0, "fresh_total_diff": 0, "repeat_diff": 0, "failed": 0}
STAND_IN = {
    "calls/colsum.py": """
from types import SimpleNamespace

import numpy as np

KEYS = {"scale"}


def make(cell, seed):
    k = cell.traffic["scale"]
    return lambda x: SimpleNamespace(total=np.asarray(x, np.int64).sum(axis=0) * k)


def same(a, b):
    return bool((a.total == b.total).all())


def control(cell, seed):
    k = cell.traffic["scale"]
    return lambda x: SimpleNamespace(total=(np.asarray(x, np.int8) * k).sum(axis=0,
                                                                           dtype=np.int8))


def flip(cell, seed):
    call = make(cell, seed)

    def flipped(x):
        total = call(x).total.copy()
        total[seed % len(total)] += 1
        return SimpleNamespace(total=total)

    return flipped
""",
    "reference/colsum.py": """
def verify(config, traffic, x, output, threads=1):
    want = [traffic["scale"] * sum(int(v) for v in col) for col in x.T]
    return {"total_diff": max(abs(int(a) - b) for a, b in zip(output.total, want))}
""",
    "data/codes.py": """
import numpy as np


def sample(n, m, density, seed, network):
    return np.random.default_rng([network, *seed]).integers(0, 3, size=(m, n))
""",
}


def make_root(tmp_path: Path) -> Path:
    """A checkout holding the benchmark's code and one more configuration,
    traffic mix, per-layer metric and cell, each added only as files and
    entries; and a cell of a stand-in entry point (``codes.codes_mix``),
    likewise only files and entries."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(TINY))
    (root / "bench" / "traffic" / "tiny_mix.json").write_text(json.dumps(MIX))
    (root / "bench" / "limits" / "tiny.tiny_mix.json").write_text(json.dumps(LIMITS))
    (root / "bench" / "metrics" / "graphs_seen.py").write_text(
        "def read(run):\n    return len(run.graphs)\n")
    for rel, text in STAND_IN.items():
        (root / "bench" / rel).write_text(text)
    (root / "bench" / "configs" / "codes.json").write_text(json.dumps(CODES))
    (root / "bench" / "traffic" / "codes_mix.json").write_text(json.dumps(CODES_MIX))
    (root / "bench" / "limits" / "codes.codes_mix.json").write_text(json.dumps(CODES_LIMITS))
    bm["configs"].append({"name": "codes", "source": "test", "file": "bench/configs/codes.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": "codes.codes_mix", "config": "codes",
                            "traffic": "codes_mix", "chips": 1, "why": "test"})
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


def _profile(*planes):
    """A stand-in for jax.profiler.ProfileData: planes of lines of events
    (name, start_ns, duration_ns)."""
    ev = lambda n, t, d: SimpleNamespace(name=n, start_ns=t, duration_ns=d)  # noqa: E731
    return SimpleNamespace(planes=[
        SimpleNamespace(name=name, lines=[SimpleNamespace(name=ln, events=[ev(*e) for e in evs])
                                          for ln, evs in lines.items()])
        for name, lines in planes])


def test_empty_device_plane_is_no_device():
    """A v5e trace carries an empty /device:CUSTOM:Megascale Trace plane:
    busy time, idle share, top operations and idle gaps read the TPU alone."""
    ms = 1_000_000
    tpu = ("/device:TPU:0", {"XLA Ops": [("a", 10 * ms, 20 * ms), ("b", 50 * ms, 10 * ms)]})
    host = ("/host:CPU", {"python": [("bench_window", 0, 100 * ms),
                                     ("total/level1", 5 * ms, 60 * ms)]})
    empty = ("/device:CUSTOM:Megascale Trace", {"Steps": []})
    views = [TraceView.from_profile(_profile(*planes))
             for planes in ([tpu, host], [empty, tpu, host], [tpu, empty, host])]
    idle = registry.metric_reader("device_idle_share")
    for view in views:
        run_ = SimpleNamespace(trace=view, window=(0, 100 * ms))
        assert list(view.chips) == ["/device:TPU:0"]
        assert view.busy_ns([(0, 100 * ms)]) == 30 * ms
        assert idle(run_) == pytest.approx(70.0)
        assert view.top_ops(0, 100 * ms) == [["a", 0.02], ["b", 0.01]]
        assert view.idle_gaps(0, 100 * ms) == views[0].idle_gaps(0, 100 * ms)
    assert registry.metric_reader("device_idle_share")(
        SimpleNamespace(trace=TraceView.from_profile(_profile(empty, host)),
                        window=(0, 100 * ms))) is None


def test_idle_chip_counts_as_idle():
    """A chip that ran nothing is a device: with two TPU planes, one of them
    empty, busy time and idle share are the average of the two chips."""
    ms = 1_000_000
    tpu0 = ("/device:TPU:0", {"XLA Ops": [("a", 10 * ms, 20 * ms), ("b", 50 * ms, 10 * ms)]})
    tpu1 = ("/device:TPU:1", {"XLA Ops": []})
    host = ("/host:CPU", {"python": [("bench_window", 0, 100 * ms),
                                     ("total/level1", 5 * ms, 60 * ms)]})
    view = TraceView.from_profile(_profile(tpu0, tpu1, host))
    run_ = SimpleNamespace(trace=view, window=(0, 100 * ms))
    assert list(view.chips) == ["/device:TPU:0", "/device:TPU:1"]
    assert view.busy_ns([(0, 100 * ms)]) == 15 * ms
    assert registry.metric_reader("device_idle_share")(run_) == pytest.approx(85.0)
    assert view.top_ops(0, 100 * ms) == [["a", 0.01], ["b", 0.005]]
    # idle 70 + 100 ms, of which total/level1 ([5, 65) ms) covers 30 + 60 ms
    assert registry.metric_reader("idle_unattributed_share")(run_) == pytest.approx(
        100.0 * (170 - 90) / 170)


def test_clipped_equals_the_plain_sum():
    """``clipped``'s bisection gives the sum over every interval it would
    have visited one by one."""
    rng = np.random.default_rng(2**31 + 11)
    for _ in range(200):
        starts = rng.integers(0, 1000, size=rng.integers(0, 30))
        merged = union((int(a), int(a + rng.integers(1, 80))) for a in starts)
        lo, hi = sorted(int(t) for t in rng.integers(-50, 1100, size=2))
        plain = sum(max(0, min(b, hi) - max(a, lo)) for a, b in merged)
        assert clipped(merged, lo, hi) == plain


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


def _with_key(root, mix, key):
    """The mix file ``mix`` with one more key."""
    path = root / "bench" / "traffic" / f"{mix}.json"
    path.write_text(json.dumps({**json.loads(path.read_text()), key: 2}))


def test_mix_key_the_generator_ignores_is_refused(tmp_path):
    root = make_root(tmp_path)
    _with_key(root, "tiny_mix", "in_flight")
    with pytest.raises(KeyError, match="in_flight"):
        registry.load_cell("tiny.tiny_mix", root)


def test_mix_key_of_another_call_is_refused(tmp_path):
    """``scale`` is the stand-in call's key: taken there, refused for pc,
    and a key that neither the generator nor the call acts on is refused."""
    root = make_root(tmp_path)
    codes = registry.load_cell("codes.codes_mix", root)
    assert len(traffic.make_pool(codes.config, codes.traffic, root)) == 3
    _with_key(root, "codes_mix", "in_flight")
    with pytest.raises(KeyError, match="in_flight"):
        registry.load_cell("codes.codes_mix", root)
    _with_key(root, "tiny_mix", "scale")
    with pytest.raises(KeyError, match="scale"):
        registry.load_cell("tiny.tiny_mix", root)


def test_config_dtype_gives_integer_datasets(tmp_path):
    root = make_root(tmp_path)
    cell = registry.load_cell("codes.codes_mix", root)
    pool = traffic.make_pool(cell.config, cell.traffic, root)
    fresh = traffic.make_fresh(cell.config, cell.traffic, 2**31 + 9, root)
    for x in pool + fresh:
        assert x.dtype == np.int32 and x.shape == (100, 5)
        assert set(np.unique(x)) <= {0, 1, 2}


@pytest.mark.parametrize("cell, kw", [
    ("nci60.full", {"max_level": None, "sepset_depth": 8, "orient": True}),
    ("dream5.order1", {"max_level": 1, "sepset_depth": 1, "orient": False}),
    ("nci60.order1", {"max_level": 1, "sepset_depth": 1, "orient": False}),
])
def test_pc_call_issues_the_same_pc_call(monkeypatch, cell, kw):
    """The committed cells name no call, so they time ``pc`` through
    bench/calls/pc.py with exactly these arguments."""
    from repro import core

    seen = []
    monkeypatch.setattr(core, "pc", lambda x, **a: seen.append((x, a)) or "out")
    c = registry.load_cell(cell)
    assert "call" not in c.config and c.call.__file__.endswith("calls/pc.py")
    assert run.program(c, 3)("x") == "out"
    assert seen == [("x", {"engine": "auto", "corr": "kernel", "alpha": 0.01, **kw})]


def stand_in_run(tmp_path, capsys, trace=0, make_call=run.program):
    root = make_root(tmp_path)
    rc = run.main(["--workload", "codes.codes_mix", "--seed", str(2**31 + 3), "--seconds", "0",
                   "--trace", str(trace)], root=root, need_chip=False, make_call=make_call)
    return rc, json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_another_entry_point_runs_correct(tmp_path, capsys, trace):
    """A call module with its own output, mix key, ``same`` and reference
    runs through run.main to a correct result; the per-layer metrics that
    every cell reports find nothing of theirs to read and are left out."""
    rc, res = stand_in_run(tmp_path, capsys, trace)
    assert rc == 0 and res["correct"] is True
    assert res["attempted"] == 3 and res["failed"] == 0
    assert res["checks"] == {name: {"value": 0, "limit": 0} for name in CODES_LIMITS}
    if trace:
        assert res["metrics"] == {} and "device_ops" in res["breakdown"]
    else:
        assert set(res["metrics"]) == {"graph_s", "setup_s"}


def test_another_entry_point_wrong_answer_is_not_correct(tmp_path, capsys):
    def off_by_one(cell, seed):
        call = run.program(cell, seed)
        return lambda x: SimpleNamespace(total=call(x).total + 1)

    rc, res = stand_in_run(tmp_path, capsys, make_call=off_by_one)
    assert rc == 0 and res["correct"] is False
    assert res["checks"]["total_diff"]["value"] == 1


def test_another_entry_point_brings_its_own_roles(tmp_path, capsys):
    """bench/control.py finds the control and the planted fault in the
    stand-in's call module: the program reads correct, both others not."""
    from bench import control

    root = make_root(tmp_path)
    seed = 2**31 + 21
    rc = control.main(["--workload", "codes.codes_mix", "--as", "program", "control", "flip",
                       "--seeds", str(seed)], root=root, need_chip=False)
    assert rc == 0
    prog, ctl, flip = (json.loads(ln) for ln in capsys.readouterr().out.strip().splitlines()[1::2])
    assert prog["correct"] is True
    assert ctl["correct"] is False and ctl["checks"]["total_diff"]["value"] == 256
    assert flip["correct"] is False and flip["checks"]["total_diff"]["value"] == 1
