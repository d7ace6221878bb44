"""Seconds per graph in the program's ``level1`` span (the level loop at
l = 1, synced at the span's exit)."""
from bench.outputs import span_mean


def read(run):
    return span_mean(run, "level1")
