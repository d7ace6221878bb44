"""Seconds per graph in the program's ``sync`` spans, at any depth: the
host blocked on the device, in a span's wait for its arrays or in a read
back through ``obs.fetch``."""
from bench.outputs import span_mean


def read(run):
    return span_mean(run, "sync")
