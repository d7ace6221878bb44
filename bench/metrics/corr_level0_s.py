"""Seconds per graph in the program's ``level0`` span: the correlation
kernel and level 0 together, since ``pc`` enters the span before the
correlation matrix is ready, so the span waits for the kernel too."""
from bench.outputs import span_mean


def read(run):
    return span_mean(run, "level0")
