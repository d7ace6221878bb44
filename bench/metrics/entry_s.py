"""Seconds per graph in the program's ``upload``, ``validate`` and ``corr``
spans: the host path of a ``pc`` call before level 0 (x to the device, the
admission checks with their read-back of x, the correlation kernel's
dispatch). None where the program has none of these spans."""
PARTS = ("upload", "validate", "corr")


def read(run):
    v = [sum(g.output.timings_s.get(k, 0.0) for k in PARTS) for g in run.graphs
         if g.output is not None and any(k in g.output.timings_s for k in PARTS)]
    return sum(v) / len(v) if v else None
