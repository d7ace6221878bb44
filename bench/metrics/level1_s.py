"""Seconds per graph in the program's ``level1`` span (the level loop at
l = 1, synced at the span's exit)."""


def read(run):
    v = [g.output.timings_s["level1"] for g in run.graphs
         if g.output is not None and "level1" in g.output.timings_s]
    return sum(v) / len(v) if v else None
