"""Seconds per graph in the program's ``readback`` span: the downloads of
the skeleton, the CPDAG and the sepsets that make the result."""


def read(run):
    v = [g.output.timings_s["readback"] for g in run.graphs
         if g.output is not None and "readback" in g.output.timings_s]
    return sum(v) / len(v) if v else None
