"""What the per-layer readers take from a call's outputs: the program's
spans (``timings_s``) and level records (``level_stats``). An output that
keeps neither, as another entry point's may not, and a graph that raised
give nothing and raise nothing, so a metric that every cell reports reads
None in a cell whose call keeps no such record."""
from __future__ import annotations


def timings(output) -> dict:
    """Seconds by span name; empty where the output keeps no spans."""
    return getattr(output, "timings_s", None) or {}


def level_stats(output) -> list | None:
    """The output's per-level records; None where it keeps none."""
    return getattr(output, "level_stats", None)


def span_mean(run, name: str) -> float | None:
    """Seconds per graph in the span ``name``, mean over the window's graphs
    that have it; None where none has."""
    v = [timings(g.output)[name] for g in run.graphs if name in timings(g.output)]
    return sum(v) / len(v) if v else None
