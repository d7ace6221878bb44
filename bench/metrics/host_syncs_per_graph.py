"""Blocking device reads per graph, as the program counts them
(``PCRun.counts["host_syncs"]``), mean over the window's graphs. None
where the program keeps no such count."""


def read(run):
    v = [(getattr(g.output, "counts", None) or {}).get("host_syncs")
         for g in run.graphs if g.output is not None]
    v = [c for c in v if c is not None]
    return sum(v) / len(v) if v else None
