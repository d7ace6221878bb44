"""Seconds per graph in the program's ``level0`` span: the correlation
kernel and level 0 together, since ``pc`` enters the span before the
correlation matrix is ready, so the span waits for the kernel too."""


def read(run):
    v = [g.output.timings_s["level0"] for g in run.graphs
         if g.output is not None and "level0" in g.output.timings_s]
    return sum(v) / len(v) if v else None
