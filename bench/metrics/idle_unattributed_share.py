"""Share (%) of the device's idle time in the traced window that no program
span names: the idle stretches of each device not covered by an annotation
whose name starts with ``total/`` (a child span of a ``pc`` call), over all
of its idle time in the window, summed over the chips (bench/trace_view.py
says which device planes are chips). None without a chip, without idle time,
or without such annotations."""
from bench.trace_view import clipped, union


def read(run):
    trace = run.trace
    if trace is None or not trace.chips:
        return None
    named = union((a, b) for n, a, b in trace.host if n.startswith("total/"))
    if not named:
        return None
    lo, hi = run.window
    idle = unnamed = 0.0
    for ops in trace.chips.values():
        busy = union((max(a, lo), min(b, hi)) for _, a, b in ops if b > lo and a < hi)
        edges = [lo] + [t for ab in busy for t in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                idle += b - a
                unnamed += (b - a) - clipped(named, a, b)
    return 100.0 * unnamed / idle if idle > 0 else None
