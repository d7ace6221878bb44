"""Seconds per graph in the program's ``chunk`` spans: the host issuing the
chunk programs of levels l >= 2 (a chunk span waits for nothing). None
where no level ran a chunk program."""


def read(run):
    v = [g.output.timings_s["chunk"] for g in run.graphs
         if g.output is not None and "chunk" in g.output.timings_s]
    return sum(v) / len(v) if v else None
