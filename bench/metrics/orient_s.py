"""Seconds per graph in the program's ``orient`` span (skeleton to
CPDAG)."""
from bench.outputs import span_mean


def read(run):
    return span_mean(run, "orient")
