"""Seconds per graph in the program's ``chunk`` spans: the host issuing the
chunk programs of levels l >= 2 (a chunk span waits for nothing). None
where no level ran a chunk program."""
from bench.outputs import span_mean


def read(run):
    return span_mean(run, "chunk")
