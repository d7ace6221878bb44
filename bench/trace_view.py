"""Reduction of a ``jax.profiler`` trace (``.xplane.pb``) to what the
per-layer metrics read: device operations and host spans on one clock.

* device operations: the events of each device plane (``/device:TPU:<k>``)
  on its op line (``XLA Ops``, or every line of the plane when it has none);
* annotations: the events of the host thread that opened the benchmark's
  ``bench_window`` annotation: the ``jax.profiler.TraceAnnotation`` spans
  of the benchmark (``bench_window``, ``graph``) and of the program's
  tracer (``total``, ``total/level<l>``, ``total/orient``, ...), besides
  the Python profiler's frames (named ``$...``), which are dropped;
* runtime events: the other host threads' events (transfers, launches,
  layout transposes), which name what the host was doing in a gap.

The program's tracer closes a span's annotation when the span's Python
block ends and only then waits for the span's device work, so an
annotation covers the dispatch, not the execution. A level's device work
is therefore read over its *phase*: from its annotation's start to the
start of the next annotation under the same parent, or the end of the
enclosing ``graph`` annotation.

Busy time is the union of a device's operation intervals; with several
devices it is averaged over them.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

DEVICE_PREFIX = "/device:"
HOST_PREFIX = "/host:"
OP_LINE = "XLA Ops"
WINDOW = "bench_window"
GRAPH = "graph"


def union(intervals) -> list:
    """Merge (start, end) intervals into disjoint ones, sorted."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clipped(merged, lo, hi) -> float:
    """Length of disjoint intervals inside [lo, hi]."""
    return float(sum(max(0.0, min(b, hi) - max(a, lo)) for a, b in merged))


def _parent(name: str) -> str:
    return name.rsplit("/", 1)[0] if "/" in name else ""


@dataclass
class TraceView:
    #: per device plane: [(op name, start_ns, end_ns)]
    devices: dict = field(default_factory=dict)
    #: [(annotation name, start_ns, end_ns)]
    host: list = field(default_factory=list)
    #: [(event name, start_ns, end_ns)] of the other host threads
    runtime: list = field(default_factory=list)

    @classmethod
    def from_dir(cls, log_dir: str) -> "TraceView":
        files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                                 recursive=True))
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
        from jax.profiler import ProfileData

        return cls.from_profile(ProfileData.from_file(files[-1]))

    @classmethod
    def from_profile(cls, pd) -> "TraceView":
        view = cls()
        for plane in pd.planes:
            lines = list(plane.lines)
            if plane.name.startswith(DEVICE_PREFIX):
                ops = [ln for ln in lines if ln.name == OP_LINE] or lines
                view.devices[plane.name] = [
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for ln in ops for e in ln.events if e.duration_ns > 0]
            elif plane.name.startswith(HOST_PREFIX):
                for ln in lines:
                    evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in ln.events if e.duration_ns > 0]
                    if any(n == WINDOW for n, _, _ in evs):
                        view.host += [ev for ev in evs if not ev[0].startswith("$")]
                    else:
                        view.runtime += evs
        return view

    def spans(self, name: str) -> list:
        """(start, end) of every annotation called ``name`` (or whose slash
        path ends in ``/name``)."""
        return sorted((a, b) for n, a, b in self.host
                      if n == name or n.endswith("/" + name))

    def phases(self, name: str) -> list:
        """(start, end) of each ``name`` annotation's phase: to the start of
        the next annotation with the same parent, else to the end of the
        enclosing ``graph`` annotation, else to its own end."""
        out = []
        for n, a, b in sorted(self.host, key=lambda t: t[1]):
            if n != name and not n.endswith("/" + name):
                continue
            graph_end = min((e for g, s, e in self.host if g == GRAPH and s <= a <= e),
                            default=b)
            nxt = min((s for m, s, _ in self.host
                       if s >= b and _parent(m) == _parent(n) and m != n and s <= graph_end),
                      default=graph_end)
            out.append((a, max(b, nxt)))
        return out

    def busy_ns(self, intervals) -> float:
        """Device-busy time inside the given disjoint host intervals,
        averaged over the devices."""
        if not self.devices:
            return 0.0
        total = 0.0
        for ops in self.devices.values():
            merged = union((a, b) for _, a, b in ops)
            total += sum(clipped(merged, lo, hi) for lo, hi in intervals)
        return total / len(self.devices)

    def top_ops(self, lo, hi, k: int = 10) -> list:
        """[name, seconds] of the k operations with the most device time
        inside [lo, hi], averaged over the devices."""
        per = {}
        for ops in self.devices.values():
            for name, a, b in ops:
                d = max(0.0, min(b, hi) - max(a, lo))
                if d > 0:
                    per[name] = per.get(name, 0.0) + d
        top = sorted(per.items(), key=lambda t: -t[1])[:k]
        return [[name, ns / 1e9 / len(self.devices)] for name, ns in top]

    def idle_gaps(self, lo, hi, k: int = 10) -> list:
        """[what the host was doing, seconds] of the k longest stretches
        inside [lo, hi] in which the first device ran nothing: the
        innermost annotation around the gap's middle, and after a ``|``
        the runtime event that covers most of the gap, if any."""
        if not self.devices:
            return []
        ops = next(iter(self.devices.values()))
        merged = union((max(a, lo), min(b, hi)) for _, a, b in ops if b > lo and a < hi)
        edges = [lo] + [t for ab in merged for t in ab] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for a, b in gaps[:k]:
            mid = (a + b) / 2
            around = [(n, s, e) for n, s, e in self.host if s <= mid <= e]
            name = min(around, key=lambda t: t[2] - t[1])[0] if around else "outside any span"
            cover = max(self.runtime, default=None,
                        key=lambda t: max(0.0, min(t[2], b) - max(t[1], a)))
            if cover is not None and min(cover[2], b) > max(cover[1], a):
                name = f"{name} | {cover[0]}"
            out.append([name, (b - a) / 1e9])
        return out
