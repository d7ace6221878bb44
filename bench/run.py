#!/usr/bin/env python3
"""Benchmark of PC-stable causal discovery (cuPC) on a TPU, driven through
the program's public entry points: ``repro.core.pc``, or the one the cell's
configuration names (bench/calls/<call>.py).

    python3 bench/run.py --workload nci60.full --seed 7 --seconds 30 --trace 0

One run of one cell (an entry of BENCHMARK.json's ``workloads``):

1. set-up, timed as ``setup_s`` from the start of the process: read the
   cell's files (bench/registry.py), make its timed datasets (the same in
   every run, bench/traffic.py), and run the cell's call once on each,
   which compiles every shape the window uses (JAX's persistent compilation
   cache sits in the checkout, so only a checkout's first run compiles);
2. the window: a closed loop submits the datasets, one graph in flight,
   pass after pass, each pass in a fresh seeded order, and starts no pass
   once --seconds have gone (the first pass always runs); the pass in
   flight is let finish;
3. after the window: the device's peak memory is read; every output is
   compared with the first output on the same dataset; the same call runs
   on fresh datasets drawn from --seed; and the outputs of a seeded sample
   of the timed datasets and of the fresh ones are held to the
   configuration's plain reference (bench/reference/), which imports
   nothing of the program.

With --trace 0 the result carries the cell's end-to-end metrics; with
--trace 1 the window runs under ``jax.profiler`` with the program's spans
annotated, and the result carries the per-layer metrics
(bench/metrics/<name>.py), the device's busy and window seconds and a
breakdown. The last line of standard output is one JSON object; the
numbers ``correct`` compares end standard error and the JSON line.

Without a TPU, or with fewer chips than the cell asks for, the run exits
with code 2 and prints no result.
"""
from __future__ import annotations

import time

_T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import registry, traffic  # noqa: E402

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


@dataclass
class Graph:
    """One submitted graph of the window."""

    dataset: int
    latency_s: float
    output: object  # the call's output (pc: a PCRun), None when the call raised
    error: str | None = None


class CompileCounter:
    """Counts backend compiles and persistent-cache hits as JAX reports
    them (jax.monitoring), while the ``with`` block lasts."""

    def __init__(self, jax):
        self.monitoring = jax.monitoring
        self.compiles = 0
        self.cache_hits = 0

    def _duration(self, event, duration_secs, **kw):
        if event == BACKEND_COMPILE:
            self.compiles += 1

    def _event(self, event, **kw):
        if event == CACHE_HIT:
            self.cache_hits += 1

    def __enter__(self):
        self.monitoring.register_event_duration_secs_listener(self._duration)
        self.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        self.monitoring.unregister_event_duration_listener(self._duration)
        self.monitoring.unregister_event_listener(self._event)
        return False

    def total(self) -> int:
        return self.compiles + self.cache_hits


def run_window(call, pool, mix, seed, seconds, annotate=None,
               clock=time.monotonic) -> tuple:
    """The closed loop. Returns (graphs, window seconds): the window runs
    from the first submission to the return of the last graph."""
    graphs = []
    t0 = clock()
    p = 0
    while p == 0 or clock() - t0 < seconds:
        for k in traffic.pass_order(mix, seed, p):
            ts = clock()
            try:
                if annotate is None:
                    out, err = call(pool[k]), None
                else:
                    with annotate("graph"):
                        out, err = call(pool[k]), None
            except Exception as e:  # a failed graph is counted, the loop goes on
                out, err = None, f"{type(e).__name__}: {e}"
            graphs.append(Graph(k, clock() - ts, out, err))
        p += 1
    return graphs, clock() - t0


def end_to_end(name: str, graphs, window_s: float, setup_s: float) -> float:
    """The end-to-end metrics, all from the host clock."""
    lat = [g.latency_s for g in graphs]
    if name == "graph_s":
        return window_s / len(graphs)
    if name == "graph_p95_s":
        return statistics.quantiles(lat, n=100, method="inclusive")[94]
    if name == "setup_s":
        return setup_s
    raise KeyError(f"bench/run.py computes no end-to-end metric {name!r}")


def verify(cell, pool, graphs, fresh, seed, root: Path = ROOT) -> dict:
    """The numbers ``correct`` compares: the reference's over a seeded
    sample of the timed datasets, and (prefixed ``fresh_``) over the fresh
    ones, given as (samples, output) pairs; outputs that differ from the
    first output on the same dataset (the call's ``same``); graphs that
    raised."""
    first, repeat = {}, 0
    for g in graphs:
        if g.output is None:
            continue
        if g.dataset not in first:
            first[g.dataset] = g.output
        elif not cell.call.same(g.output, first[g.dataset]):
            repeat += 1
        else:
            g.output = first[g.dataset]  # identical: keep one copy
    ref = registry.load_module(
        root / "bench" / "reference" / f"{cell.config['reference']}.py",
        f"bench_reference_{cell.config['reference']}")
    threads = min(8, os.cpu_count() or 1)
    numbers = {}
    held = [("", pool[k], first[k]) for k in traffic.checked(cell.traffic, seed) if k in first]
    held += [("fresh_", x, out) for x, out in fresh if out is not None]
    for prefix, x, out in held:
        for name, v in ref.verify(cell.config, cell.traffic, x, out, threads=threads).items():
            numbers[prefix + name] = max(numbers.get(prefix + name, v), v)
    numbers["repeat_diff"] = repeat
    numbers["failed"] = (sum(g.output is None for g in graphs)
                         + sum(out is None for _, out in fresh))
    return numbers


def program(cell, seed: int):
    """The system under test: the call the window times, as the cell's call
    module makes it."""
    return cell.call.make(cell, seed)


def memory_peak(jax) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


def main(argv=None, *, root: Path = ROOT, need_chip: bool = True, make_call=program,
         started: float = _T_START) -> int:
    """One run. ``need_chip=False`` skips the look for a TPU and leaves
    JAX's compilation cache alone, for the harness's own tests;
    ``make_call(cell, seed)`` puts another call in the program's place
    (bench/control.py); ``setup_s`` counts from ``started``."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)

    cell = registry.load_cell(a.workload, root)
    import jax

    devices = jax.devices()
    kind = devices[0].device_kind
    if need_chip:
        if devices[0].platform != "tpu" or len(devices) < cell.chips:
            print(f"bench: cell {cell.name} needs {cell.chips} TPU chip(s); JAX has "
                  f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
            return 2
        registry.peaks(kind, root)  # an unknown device is an error, not a default
        from repro.launch.compile_cache import enable_compile_cache

        print(f"bench: compilation cache {enable_compile_cache()}", file=sys.stderr)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    with CompileCounter(jax) as counter:
        result = _run(a, cell, root, jax, devices, counter, make_call(cell, a.seed), started)
    print(json.dumps(result))
    return 0


def _run(a, cell, root, jax, devices, counter, call, started) -> dict:
    from repro import obs

    pool = traffic.make_pool(cell.config, cell.traffic, root)
    for x in pool:
        call(x)
    setup_s = time.monotonic() - started
    print(f"bench: set-up {setup_s:.3f} s, {counter.compiles} compiles, "
          f"{counter.cache_hits} persistent-cache hits", file=sys.stderr)

    trace_dir, annotate = None, None
    before = counter.total()
    if a.trace:
        obs.configure(enabled=True, jax_profiler=True)
        annotate = jax.profiler.TraceAnnotation
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            prof = jax.profiler.ProfileOptions()
            prof.python_tracer_level = 0  # annotations only, no Python frames
            jax.profiler.start_trace(trace_dir, profiler_options=prof)
            with annotate("bench_window"):
                graphs, window_s = run_window(call, pool, cell.traffic, a.seed,
                                              a.seconds, annotate)
            jax.profiler.stop_trace()
        finally:
            obs.configure(enabled=False, jax_profiler=False)
    else:
        graphs, window_s = run_window(call, pool, cell.traffic, a.seed, a.seconds)
    window_compiles = counter.total() - before
    peak = memory_peak(jax)

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    metrics, breakdown = {}, None
    if a.trace:
        from bench.trace_view import TraceView

        view = TraceView.from_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        lo, hi = view.spans("bench_window")[-1]
        device["busy_s"] = view.busy_ns([(lo, hi)]) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        breakdown = {"device_ops": view.top_ops(lo, hi), "idle_gaps": view.idle_gaps(lo, hi)}
        run = SimpleNamespace(cell=cell, graphs=graphs, window_s=window_s,
                              trace=view, window=(lo, hi))
        for m in cell.per_layer:
            v = registry.metric_reader(m["name"], root)(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(end_to_end(m["name"], graphs, window_s, setup_s)),
                                  "unit": m["unit"]}

    fresh = []
    for x in traffic.make_fresh(cell.config, cell.traffic, a.seed, root):
        try:
            fresh.append((x, call(x)))
        except Exception as e:  # counted as failed, like a graph of the window
            print(f"bench: fresh dataset raised {type(e).__name__}: {e}", file=sys.stderr)
            fresh.append((x, None))
    numbers = verify(cell, pool, graphs, fresh, a.seed, root)
    checks, reported = {}, {}
    for name, value in numbers.items():
        if name not in cell.limits:
            raise KeyError(f"bench/limits/{cell.name}.json has no limit for {name!r}")
        if cell.limits[name] is None:  # no limit holds it (PERF.md says why)
            reported[name] = value
        else:
            checks[name] = {"value": value, "limit": cell.limits[name]}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    for g in graphs:
        if g.error:
            print(f"bench: graph on dataset {g.dataset} raised {g.error}", file=sys.stderr)
            break
    lat = sorted(g.latency_s for g in graphs)
    print(f"bench: {len(graphs)} graphs in {window_s:.3f} s, "
          f"{window_compiles} compiles or cache loads inside the window; latency "
          f"min {lat[0]:.4f} median {statistics.median(lat):.4f} max {lat[-1]:.4f} s",
          file=sys.stderr)
    for name, value in reported.items():
        print(f"reported {name} {value!r} (no limit)", file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)

    result = {"correct": correct, "attempted": len(graphs),
              "failed": sum(g.output is None for g in graphs), "metrics": metrics,
              "device": device, "window_compiles": window_compiles}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["reported"] = reported
    result["checks"] = checks
    return result


if __name__ == "__main__":
    sys.exit(main())
