"""Seconds per graph in the program's ``level2``, ``level3``, ... spans
together (the level loop at l >= 2: planner, chunk programs, commits).
None where no graph's output keeps spans."""
from bench.outputs import timings


def read(run):
    v = [sum(t for k, t in timings(g.output).items()
             if k.startswith("level") and k[5:].isdigit() and int(k[5:]) >= 2)
         for g in run.graphs if timings(g.output)]
    return sum(v) / len(v) if v and any(v) else None
