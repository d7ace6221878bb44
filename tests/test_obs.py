"""Observability layer (repro/obs/): trace spans, metrics registry, JSONL
journals, serving telemetry, and the zero-overhead contract.

The two contracts that matter most:

* Disabled obs is invisible: no journal file is created, and pc outputs
  are BIT-IDENTICAL with obs on vs off (spans only add block_until_ready
  calls, never change what is computed).
* On a ManualClock the whole trace — span timeline, journal bytes — is
  deterministic, so journals can be asserted on, not just eyeballed.

Also here: the counter-drift guard. dispatches/col_gathers used to be
incremented in three unrelated places; record_level_stats is now the one
definition, and these tests assert the per-level stats dicts and the
registry totals agree (see also test_engines.py / test_sharding.py).
"""
import json
import os

import numpy as np
import pytest

from repro import obs

pytestmark = pytest.mark.obs

M = 400


def _x(n=12, seed=0, m=M):
    from repro.data.synthetic_dag import sample_gaussian_dag

    x, _ = sample_gaussian_dag(n=n, m=m, density=0.15, seed=seed)
    return np.asarray(x, np.float32)


# ---------------------------------------------------------------- spans
def test_span_nesting_paths_and_durations():
    clk = obs.ManualClock()
    tr = obs.Tracer("t", clock=clk)
    with tr.span("total"):
        clk.advance(1.0)
        with tr.span("level1", level=1):
            clk.advance(2.0)
        with tr.span("level2"):
            clk.advance(3.0)
    done = {s.name: s for s in tr.spans}
    assert done["level1"].path == "total/level1"
    assert done["level1"].depth == 1
    assert done["level1"].attrs["level"] == 1
    assert done["level1"].dur_s == 2.0
    assert done["level2"].dur_s == 3.0
    assert done["total"].dur_s == 6.0
    assert tr.timings() == {"level1": 2.0, "level2": 3.0, "total": 6.0}


def test_span_repeated_names_sum_in_timings():
    clk = obs.ManualClock()
    tr = obs.Tracer(clock=clk)
    for _ in range(3):
        with tr.span("chunk"):
            clk.advance(0.5)
    assert tr.timings() == {"chunk": 1.5}


def test_span_exception_safety():
    clk = obs.ManualClock()
    tr = obs.Tracer(clock=clk)
    with pytest.raises(ValueError):
        with tr.span("outer"):
            with tr.span("inner"):
                clk.advance(1.0)
                raise ValueError("boom")
    # both spans closed, error recorded, stack unwound
    assert [s.name for s in tr.spans] == ["inner", "outer"]
    assert all(s.t1 is not None for s in tr.spans)
    assert tr.spans[0].attrs["error"] == "ValueError"
    assert tr._stack == []
    with tr.span("after"):  # tracer still usable
        pass
    assert tr.spans[-1].path == "after"


def test_disabled_tracer_yields_noop_span():
    tr = obs.Tracer(enabled=False)
    with tr.span("x") as sp:
        assert sp is obs.NULL_SPAN
        sp.set(a=1).sync(np.zeros(3))  # all no-ops
    assert tr.spans == []
    assert tr.timings() == {}


# -------------------------------------------------------------- metrics
def test_metrics_labeled_aggregation():
    reg = obs.MetricsRegistry()
    reg.inc(obs.DISPATCHES, 3, engine="S", level=1)
    reg.inc(obs.DISPATCHES, 5, engine="S", level=2)
    reg.inc(obs.DISPATCHES, 7, engine="S-grid", level=1)
    assert reg.value(obs.DISPATCHES, engine="S", level=1) == 3
    assert reg.total(obs.DISPATCHES, engine="S") == 8
    assert reg.total(obs.DISPATCHES) == 15
    reg.set_gauge("depth", 4)
    reg.set_gauge("depth", 2)
    assert reg.value("depth") == 2
    reg.observe("lat", 0.003)
    reg.observe("lat", 2.0)
    fam = reg.collect()["lat"]["series"][0]
    assert fam["count"] == 2 and fam["sum"] == 2.003


def test_metrics_kind_conflict_raises():
    reg = obs.MetricsRegistry()
    reg.inc("x")
    with pytest.raises(TypeError):
        reg.set_gauge("x", 1.0)


def test_metrics_prometheus_exposition():
    reg = obs.MetricsRegistry()
    reg.inc("pc_dispatches_total", 4, engine="S", level=1)
    reg.set_gauge("pc_serve_queue_depth", 3)
    reg.observe("pc_serve_latency_seconds", 0.02)
    text = reg.expose()
    assert "# TYPE pc_dispatches_total counter" in text
    assert 'pc_dispatches_total{engine="S",level="1"} 4.0' in text
    assert "pc_serve_queue_depth 3.0" in text
    assert 'pc_serve_latency_seconds_bucket{le="+Inf"} 1' in text
    assert "pc_serve_latency_seconds_count 1" in text


def test_record_level_stats_single_definition():
    reg = obs.MetricsRegistry()
    st = {"engine": "S", "dispatches": 6, "chunks": 3, "total_sets": 100,
          "col_gathers": 3, "col_gather_bytes": 1200}
    obs.record_level_stats(st, level=2, layout="sharded", registry=reg)
    assert reg.total(obs.DISPATCHES) == 6
    assert reg.total(obs.COL_GATHERS) == 3
    assert reg.total(obs.COL_GATHER_BYTES) == 1200
    assert reg.value(obs.LEVELS, engine="S", level=2, layout="sharded") == 1
    # no col_gathers key → the gather series are untouched, not zero-bumped
    reg2 = obs.MetricsRegistry()
    obs.record_level_stats({"engine": "E", "dispatches": 2}, level=1,
                           registry=reg2)
    assert obs.COL_GATHERS not in reg2.collect()


# -------------------------------------------------------------- journal
def test_journal_schema_round_trip(tmp_path):
    path = str(tmp_path / "j.jsonl")
    clk = obs.ManualClock()
    jr = obs.Journal(path)
    tr = obs.Tracer("run", clock=clk, journal=jr)
    with tr.span("total"):
        clk.advance(1.0)
        with tr.span("level1", chunks=2):
            clk.advance(0.5)
    tr.finish(driver="test")
    recs = obs.read_journal(path)
    assert [r["kind"] for r in recs] == ["span", "span", "run"]
    assert all(r["schema"] == obs.SCHEMA_VERSION for r in recs)
    lv = next(r for r in recs if r.get("name") == "level1")
    assert lv["path"] == "total/level1"
    assert lv["dur_s"] == 0.5
    assert lv["attrs"] == {"chunks": 2}
    run = recs[-1]
    assert run["timings_s"] == {"level1": 0.5, "total": 1.5}
    assert obs.phase_summary(recs, depth=1) == {"level1": 0.5}


def test_journal_deterministic_under_manual_clock(tmp_path):
    def one(path):
        clk = obs.ManualClock()
        tr = obs.Tracer("run", clock=clk, journal=obs.Journal(path))
        with tr.span("total", cfg="x"):
            clk.advance(2.0)
            with tr.span("phase"):
                clk.advance(1.0)
        tr.finish(seed=0)
        with open(path, encoding="utf-8") as fh:
            return fh.read()

    a = one(str(tmp_path / "a.jsonl"))
    b = one(str(tmp_path / "b.jsonl"))
    assert a == b  # byte-identical journals on virtual time


def test_journal_lazy_open_leaves_no_file(tmp_path):
    path = str(tmp_path / "never.jsonl")
    jr = obs.Journal(path)
    jr.close()
    assert not os.path.exists(path)


# -------------------------------------------- driver integration + gating
def test_pc_journal_spans_reconcile_with_total(tmp_path):
    from repro.core.pc import pc

    path = str(tmp_path / "pc.jsonl")
    x = _x()
    with obs.scoped(enabled=True, journal_path=path):
        run = pc(x, alpha=0.01)
    recs = obs.read_journal(path)
    spans = [r for r in recs if r["kind"] == "span"]
    depths = sorted({r["depth"] for r in spans})
    by_depth = {d: obs.phase_summary(recs, depth=d) for d in depths}
    # every timings_s phase appears in the journal with the same duration,
    # summed over the depths it occurs at (``sync`` and ``degree`` sit both
    # directly under ``total`` and inside a level)
    for k, v in run.timings_s.items():
        if k == "total":
            continue
        got = sum(by_depth[d].get(k, 0.0) for d in depths)
        assert got == pytest.approx(v)
    assert set(run.timings_s) == {r["name"] for r in spans}
    # at each depth the children fit inside their parents, and the depth-1
    # phases account for at least half of the total
    phases = by_depth[1]
    total = run.timings_s["total"]
    assert sum(phases.values()) <= total + 1e-6
    assert sum(phases.values()) >= 0.5 * total
    for sp in spans:
        kids = [r["dur_s"] for r in spans if r["depth"] == sp["depth"] + 1
                and r["path"].rsplit("/", 1)[0] == sp["path"]
                and sp["t0"] <= r["t0"] <= sp["t1"]]
        assert sum(kids) <= sp["dur_s"] + 1e-6
    run_rec = [r for r in recs if r["kind"] == "run"]
    assert len(run_rec) == 1 and run_rec[0]["timings_s"] == run.timings_s
    assert run_rec[0]["counts"] == run.counts


def test_zero_overhead_contract_disabled_obs(tmp_path):
    """Disabled obs: no journal file, bit-identical pc outputs on/off."""
    from repro.core.pc import pc

    x = _x(seed=3)
    assert not obs.enabled()
    base = pc(x, alpha=0.01)
    path = str(tmp_path / "on.jsonl")
    with obs.scoped(enabled=True, journal_path=path), obs.scoped_registry():
        on = pc(x, alpha=0.01)
    off = pc(x, alpha=0.01)
    for a, b in ((base, on), (base, off)):
        np.testing.assert_array_equal(a.adj, b.adj)
        np.testing.assert_array_equal(a.cpdag, b.cpdag)
        np.testing.assert_array_equal(a.sepsets, b.sepsets)
    assert os.path.exists(path)  # enabled run journaled...
    # ...and the disabled runs wrote nothing anywhere
    assert list(tmp_path.iterdir()) == [tmp_path / "on.jsonl"]


def test_timings_populated_without_obs():
    """timings_s is a derived view of the always-on driver tracer — it
    must stay populated with the classic keys even with obs disabled."""
    from repro.core.pc import pc

    run = pc(_x(), alpha=0.01)
    assert "level0" in run.timings_s and "orient" in run.timings_s
    assert "total" in run.timings_s
    assert run.timings_s["total"] >= run.timings_s["level0"]


def test_registry_counts_match_level_stats():
    """The drift guard at the single-device seam: registry totals ==
    summed per-level stats dicts, engine-labeled."""
    from repro.core.pc import pc_from_corr
    from repro.core.cit import correlation_from_samples

    c = np.asarray(correlation_from_samples(_x(seed=5)))
    with obs.scoped(enabled=True), obs.scoped_registry() as reg:
        run = pc_from_corr(c, M, alpha=0.01, engine="S")
        want = sum(st["dispatches"] for st in run.level_stats)
        assert reg.total(obs.DISPATCHES, layout="single") == want
        assert reg.total(obs.CHUNKS, layout="single") == \
            sum(st.get("chunks", 0) for st in run.level_stats)
        assert reg.total(obs.LEVELS) == len(run.level_stats)


# ----------------------------------- annotations, span tree, host syncs
def test_annotation_brackets_span_and_its_sync(monkeypatch):
    """With the profiler on, a span's annotation opens before t0 and closes
    after its sync and t1, so its device work lies inside the annotation;
    the sync itself runs in a counted ``sync`` child span."""
    import jax
    import jax.profiler

    events = []

    class Clock(obs.ManualClock):
        def now(self):
            t = super().now()
            events.append(("now", t))
            return t

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            events.append(("enter", self.name))
            return self

        def __exit__(self, *exc):
            events.append(("exit", self.name))
            return False

    def block(a):
        clk.advance(2.0)  # the device work the span waits for
        events.append(("block", a))
        return a

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    monkeypatch.setattr(jax, "block_until_ready", block)
    clk = Clock()
    tr = obs.Tracer("t", clock=clk, profiler=True)
    with tr.span("level1") as sp:
        clk.advance(1.0)
        sp.sync("adj")
    assert events == [
        ("enter", "level1"), ("now", 0.0),
        ("enter", "level1/sync"), ("now", 1.0), ("block", "adj"),
        ("now", 3.0), ("exit", "level1/sync"),
        ("now", 3.0), ("exit", "level1"),
    ]
    done = {s.name: s for s in tr.spans}
    assert (done["level1"].t0, done["level1"].t1) == (0.0, 3.0)
    assert done["sync"].path == "level1/sync"
    assert done["sync"].attrs == {"site": "level1"}
    assert tr.counts() == {"host_syncs": 1}


def test_annotation_closes_when_the_span_raises(monkeypatch):
    import jax.profiler

    opened = []

    class Annotation:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            opened.remove(opened[-1])
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    tr = obs.Tracer(clock=obs.ManualClock(), profiler=True)
    with pytest.raises(ValueError):
        with tr.span("outer"), tr.span("inner"):
            raise ValueError("boom")
    assert opened == [] and tr._stack == []
    assert [s.attrs["error"] for s in tr.spans] == ["ValueError", "ValueError"]


def test_current_tracer_and_fetch():
    import jax.numpy as jnp

    assert obs.current() is obs.NULL_TRACER
    x = jnp.arange(4)
    # no tracer open: a plain read, nothing recorded
    np.testing.assert_array_equal(obs.fetch(x, site="t"), np.arange(4))
    assert obs.NULL_TRACER.spans == [] and obs.NULL_TRACER.counts() == {}
    tr = obs.Tracer("t", clock=obs.ManualClock())
    with obs.scoped(enabled=True), obs.scoped_registry() as reg:
        with tr.activate():
            assert obs.current() is tr
            with obs.current().span("outer"):
                obs.fetch(x, site="a")
                obs.fetch(x, site="b")
        assert obs.current() is obs.NULL_TRACER
        obs.fetch(x, site="a")  # outside the tracer: registry only
        assert reg.value(obs.HOST_SYNCS, site="a") == 2
        assert reg.value(obs.HOST_SYNCS, site="b") == 1
    assert [s.path for s in tr.spans] == ["outer/sync", "outer/sync", "outer"]
    assert [s.attrs["site"] for s in tr.spans[:2]] == ["a", "b"]
    assert tr.counts() == {"host_syncs": 2}


N_TREE = 60
LEVEL_NAME = __import__("re").compile(r"level\d")


@pytest.fixture(scope="module")
def auto_runs(tmp_path_factory):
    """One pc call at n = 60 through the kernel engines (interpret mode)
    per depth cap, journaled, with every blocking read primitive counted."""
    import jax

    from repro.core.pc import pc

    x = _x(n=N_TREE, seed=0, m=400)
    out = {}
    for cap in (1, None):
        path = str(tmp_path_factory.mktemp("tree") / "pc.jsonl")
        calls = {"device_get": 0, "block_until_ready": 0}
        real = {k: getattr(jax, k) for k in calls}

        def counted(name):
            def f(*a, **kw):
                calls[name] += 1
                return real[name](*a, **kw)
            return f

        for k in calls:
            setattr(jax, k, counted(k))
        try:
            with obs.scoped(enabled=True, journal_path=path), \
                    obs.scoped_registry() as reg:
                run = pc(x, alpha=0.01, engine="auto", max_level=cap)
                registry = reg.collect()
        finally:
            for k, f in real.items():
                setattr(jax, k, f)
        out[cap] = (run, obs.read_journal(path), calls, registry)
    return out


def _children(recs, parent="total"):
    return [r["name"] for r in recs if r["kind"] == "span"
            and r["path"] == f"{parent}/{r['name']}"]


@pytest.mark.parametrize("cap", [1, None], ids=["max_level1", "full_depth"])
def test_pc_span_tree(auto_runs, cap):
    run, recs, _, _ = auto_runs[cap]
    kids = _children(recs)
    levels = [f"level{ell}" for ell in range(1, run.levels_run + 1)]
    want = ["upload", "validate", "corr", "level0"]
    for lv in levels:
        want += ["degree", lv]
    if cap is None:
        want += ["degree"]  # the read that finds no further level to run
    want += ["orient", "readback"]
    assert kids == want
    if cap is None:
        assert run.levels_run >= 2
    names = {r["name"] for r in recs if r["kind"] == "span"}
    assert {n for n in names if LEVEL_NAME.match(n)} == {"level0", *levels}
    assert set(run.timings_s) == names
    # each level first reads back its threshold; level 1 (dense kernel)
    # reads its own degree; levels >= 2 plan, then issue one chunk span per
    # class program: the classes in order, ranks ascending within each
    spans = [r for r in recs if r["kind"] == "span"]
    assert _children(recs, "total/level0") == ["sync", "sync"]
    assert _children(recs, "total/level1") == ["sync", "degree", "sync"]
    for st in run.level_stats:
        if st["level"] < 2:
            continue
        lv = f"total/level{st['level']}"
        assert _children(recs, lv) == (["sync", "plan"] + ["chunk"] * st["chunks"]
                                       + ["sync"])
        got = [(r["attrs"]["cls"], r["attrs"]["t0"]) for r in spans
               if r["path"] == f"{lv}/chunk"]
        assert got == [(k["bucket"], t0) for k in st["classes"]
                       for t0 in range(0, k["total"], k["n_chunk"])]
    # every sync span names its site; the readback reads three arrays
    assert all("site" in r["attrs"] for r in spans if r["name"] == "sync")
    assert _children(recs, "total/readback") == ["sync"] * 3
    # the children of total cover it, up to the tracer's own bookkeeping
    top = sum(r["dur_s"] for r in spans if r["depth"] == 1)
    assert top <= run.timings_s["total"] + 1e-9


@pytest.mark.parametrize("cap,want", [(1, 12), (None, 25)],
                         ids=["max_level1", "full_depth"])
def test_pc_host_syncs_exact(auto_runs, cap, want):
    """The blocking reads of a fixed small run, counted exactly: validate's
    read of x, level 0's threshold and sync, per level a degree read, its
    threshold, the level's own degree or plan read and its sync, the read
    that ends the loop, orient's degree read and sync, and three readbacks.
    A new read fails here."""
    run, recs, calls, registry = auto_runs[cap]
    assert run.levels_run == (1 if cap == 1 else 4)
    assert run.counts["host_syncs"] == want
    assert calls["device_get"] + calls["block_until_ready"] == want
    syncs = [r for r in recs if r["kind"] == "span" and r["name"] == "sync"]
    assert len(syncs) == want
    series = registry[obs.HOST_SYNCS]["series"]
    assert sum(s["value"] for s in series) == want
    sites = {s["labels"]["site"] for s in series}
    assert {"validate", "cit.threshold", "level0", "pc.degree",
            "engines.dense_l1_degree", "level1", "pc.orient_degree", "orient",
            "pc.readback"} <= sites
    assert ("levels.plan" in sites) == (cap is None)


def test_pc_outputs_identical_with_profiler_annotations(auto_runs):
    """The spans, syncs and annotations change no output bit: the same call
    with obs off, and with obs and profiler annotations on."""
    from repro.core.pc import pc

    ref, _, _, _ = auto_runs[1]
    x = _x(n=N_TREE, seed=0, m=400)
    off = pc(x, alpha=0.01, engine="auto", max_level=1)
    with obs.scoped(enabled=True, jax_profiler=True), obs.scoped_registry():
        on = pc(x, alpha=0.01, engine="auto", max_level=1)
    for run in (off, on):
        np.testing.assert_array_equal(run.adj, ref.adj)
        np.testing.assert_array_equal(run.cpdag, ref.cpdag)
        np.testing.assert_array_equal(run.sepsets, ref.sepsets)
        assert run.counts == ref.counts


def test_scan_engine_span_tree():
    from repro.core.pc import pc

    run = pc(_x(seed=2), alpha=0.01, engine="scan", max_level=2)
    assert list(run.timings_s) == ["upload", "sync", "validate", "corr",
                                   "scan", "readback", "total"]
    # validate, the thresholds of levels 0-2, the scan program's plan read
    # and sync, four readbacks
    assert run.counts == {"host_syncs": 10}


def test_pc_from_corr_opens_its_own_tracer():
    from repro.core.cit import correlation_from_samples
    from repro.core.pc import pc_from_corr

    c = np.asarray(correlation_from_samples(_x(seed=5)))
    run = pc_from_corr(c, M, alpha=0.01, engine="S", max_level=1)
    assert obs.current() is obs.NULL_TRACER
    assert {"validate", "upload", "level0", "readback", "total"} <= set(run.timings_s)
    assert "corr" not in run.timings_s
    # a host C is validated without a read; the device reads as in pc
    assert run.counts["host_syncs"] >= 5


# ---------------------------------------------------------------- serving
def _serve_x(n=12, seed=1):
    return _x(n=n, seed=seed)


def test_service_latency_breakdown_and_counters():
    from repro.serve import ManualClock, PCService, Request, ServeConfig

    clk = ManualClock()
    svc = PCService(ServeConfig(slot_size=4), clock=clk)
    svc.submit(Request(rid="r1", x=_serve_x(), alpha=0.01, max_level=2))
    clk.advance(0.25)  # queue wait before the dispatch loop runs
    rep = svc.drain()
    g = rep.result("r1")
    assert g.queue_wait_s == pytest.approx(0.25)
    assert g.dispatch_s >= 0.0 and g.assembly_s >= 0.0
    assert svc.metrics.value("pc_serve_requests_total",
                             outcome="admitted") == 1
    assert svc.metrics.total("pc_serve_deliveries_total") == 1
    assert svc.metrics.value("pc_serve_queue_depth") == 0
    text = svc.metrics_text()
    assert 'pc_serve_deliveries_total{tier="slot"} 1.0' in text


def test_service_deadline_miss_and_retry_counters():
    from repro.serve import FaultPlan, ManualClock, PCService, Request, \
        ServeConfig

    clk = ManualClock()
    faults = FaultPlan(cert_miss={"r-miss": 1}, slot_delay={"r-late": 9.0})
    svc = PCService(ServeConfig(slot_size=2, backoff_s=0.01), clock=clk,
                    faults=faults)
    svc.submit(Request(rid="r-late", x=_serve_x(seed=2), max_level=2,
                       timeout_s=2.0))
    svc.submit(Request(rid="r-miss", x=_serve_x(seed=3), max_level=2))
    rep = svc.drain()
    assert any(d.rid == "r-late" and d.code == "deadline"
               for d in rep.dead_letters)
    assert svc.metrics.total("pc_serve_deadline_miss_total") >= 1
    assert svc.metrics.value("pc_serve_retries_total",
                             reason="cert_miss") >= 1
    assert svc.metrics.value("pc_serve_dead_letters_total",
                             code="deadline") >= 1
    assert rep.result("r-miss").exact  # the retry ladder still delivered


def test_service_journal_serve_records(tmp_path):
    from repro.serve import ManualClock, PCService, Request, ServeConfig

    path = str(tmp_path / "serve.jsonl")
    with obs.scoped(enabled=True, journal_path=path):
        svc = PCService(ServeConfig(slot_size=4), clock=ManualClock())
        svc.submit(Request(rid="r1", x=_serve_x(seed=4), max_level=2))
        svc.drain()
    recs = obs.read_journal(path)
    kinds = {r["event"] for r in recs if r["kind"] == "serve"}
    assert {"admit", "slot_dispatch", "delivered"} <= kinds
    dl = next(r for r in recs if r.get("event") == "delivered")
    for field in ("queue_wait_s", "dispatch_s", "assembly_s", "latency_s"):
        assert field in dl
    assert all(json.dumps(r) for r in recs)  # every record JSON-clean


def test_service_outputs_identical_with_obs_on_off(tmp_path):
    from repro.serve import ManualClock, PCService, Request, ServeConfig

    x = _serve_x(seed=6)

    def run(**scope):
        with obs.scoped(**scope):
            svc = PCService(ServeConfig(slot_size=4), clock=ManualClock())
            svc.submit(Request(rid="r", x=x, max_level=2))
            return svc.drain().result("r")

    g_off = run(enabled=False)
    g_on = run(enabled=True, journal_path=str(tmp_path / "s.jsonl"))
    np.testing.assert_array_equal(g_off.adj, g_on.adj)
    np.testing.assert_array_equal(g_off.cpdag, g_on.cpdag)
    np.testing.assert_array_equal(g_off.sepsets, g_on.sepsets)
    assert g_off.latency_s == g_on.latency_s  # virtual clocks agree too
