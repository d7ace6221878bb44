"""Seconds per graph in the program's ``readback`` span: the downloads of
the skeleton, the CPDAG and the sepsets that make the result."""
from bench.outputs import span_mean


def read(run):
    return span_mean(run, "readback")
