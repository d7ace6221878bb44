"""Seconds per graph in the program's ``sync`` spans, at any depth: the
host blocked on the device, in a span's wait for its arrays or in a read
back through ``obs.fetch``."""


def read(run):
    v = [g.output.timings_s["sync"] for g in run.graphs
         if g.output is not None and "sync" in g.output.timings_s]
    return sum(v) / len(v) if v else None
