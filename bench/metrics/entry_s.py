"""Seconds per graph in the program's ``upload``, ``validate`` and ``corr``
spans: the host path of a ``pc`` call before level 0 (x to the device, the
admission checks with their read-back of x, the correlation kernel's
dispatch). None where the program has none of these spans."""
from bench.outputs import timings

PARTS = ("upload", "validate", "corr")


def read(run):
    per_graph = [timings(g.output) for g in run.graphs]
    v = [sum(t.get(k, 0.0) for k in PARTS) for t in per_graph if any(k in t for k in PARTS)]
    return sum(v) / len(v) if v else None
