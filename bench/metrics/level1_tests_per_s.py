"""Level-1 tests PC-stable needs per second of device time in the
program's level-1 phases: sum_i d_i (d_i - 1) over the graph level 1
started from, over the union of device operations from the start of each
``level1`` annotation to the start of the next one under ``total`` (see
bench/trace_view.py on why a phase and not the annotation itself)."""
from bench import work
from bench.outputs import timings


def read(run):
    if run.trace is None:
        return None
    spans = run.trace.phases("level1")
    busy = run.trace.busy_ns(spans) / 1e9
    if not spans or busy <= 0:
        return None
    needed, counted = 0, 0
    for g in run.graphs:
        if "level1" not in timings(g.output):
            continue
        needed += work.needed_tests(g.output, 1)
        counted += 1
    if counted != len(spans):
        return None  # a span the graphs do not account for: no reading
    return needed / busy
