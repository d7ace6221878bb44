"""Seconds per graph in the program's ``orient`` span (skeleton to
CPDAG)."""


def read(run):
    v = [g.output.timings_s["orient"] for g in run.graphs
         if g.output is not None and "orient" in g.output.timings_s]
    return sum(v) / len(v) if v else None
