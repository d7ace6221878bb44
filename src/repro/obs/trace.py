"""Trace spans: nestable, exception-safe, device-time-aware timing.

The repo grew its timing organically — ``time.perf_counter`` pairs in
``core/pc.py`` / ``core/distributed.py`` / ``batch/ensemble.py``, each with
its own dict-and-key convention. A :class:`Tracer` replaces all of them
with ONE seam:

* ``with tracer.span("level2", level=2) as sp`` opens a nested span; spans
  record name, slash-joined path, depth, start/end time and free-form
  attributes, and close correctly on exceptions (the error type is stamped
  into the span's attrs so a journal shows WHERE a run died).
* time flows only through an injectable clock — :class:`MonotonicClock`
  in production, :class:`ManualClock` (the serve/faults.py pattern; the
  classes now live here and serve re-exports them) in tests, which makes
  span timelines and JSONL journals byte-deterministic.
* ``sp.sync(arr, ...)`` registers device arrays the span should
  ``jax.block_until_ready`` at exit — device-time-aware wall timing that
  costs NOTHING when the tracer is disabled (the no-op span ignores the
  registration and no block is issued).
* ``profiler=True`` additionally brackets every span in a
  ``jax.profiler.TraceAnnotation``, so host spans line up with compiled-
  backend traces in TensorBoard/perfetto when a ``jax.profiler.trace`` is
  active around the run. The annotation opens before the span reads
  ``t0`` and closes after its syncs and ``t1``, so the span's device work
  lies inside its annotation on the profiler's clock.
* blocking reads are counted: :func:`fetch` is the one seam through which
  the ``pc`` host path reads device arrays back (a ``sync`` child span
  plus one ``host_syncs`` count), and a span's own ``sync()`` wait runs
  in such a child span too. ``Tracer.count`` keeps per-run counts beside
  the spans and mirrors them into the metrics registry when obs is on.
* :func:`current` returns the tracer a driver opened with
  ``Tracer.activate()`` (one context variable), or a disabled tracer, so
  deeper layers add spans without a tracer argument in every signature.

``Tracer.timings()`` is the back-compat bridge: it renders the span list
as the ``{name: seconds}`` dict the ``PCRun.timings_s`` field has always
carried, so existing callers and benchmarks keep working unchanged.
"""
from __future__ import annotations

import contextvars
import time
from contextlib import contextmanager
from dataclasses import dataclass, field

from . import metrics
from .config import enabled

#: run count of the blocking device reads (``Tracer.count``); the registry
#: series is ``pc_host_syncs_total{site}`` (metrics.HOST_SYNCS)
SYNC_COUNT = "host_syncs"


class MonotonicClock:
    """Real time — the production clock."""

    def now(self) -> float:
        return time.monotonic()


class ManualClock:
    """Virtual time the caller advances by hand. ``advance`` is also how
    injected slot delays take effect in the serving layer (serve/faults.py
    re-exports this class for back-compat)."""

    def __init__(self, t0: float = 0.0):
        self._t = float(t0)

    def now(self) -> float:
        return self._t

    def advance(self, dt: float) -> float:
        if dt < 0:
            raise ValueError(f"cannot advance time backwards (dt={dt})")
        self._t += float(dt)
        return self._t


@dataclass
class Span:
    """One finished (or open, while ``t1 is None``) trace span."""

    name: str
    path: str  # slash-joined ancestry, e.g. "total/level2"
    depth: int
    t0: float
    t1: float | None = None
    attrs: dict = field(default_factory=dict)
    _sync: tuple = ()

    @property
    def dur_s(self) -> float | None:
        return None if self.t1 is None else self.t1 - self.t0

    def set(self, **attrs) -> "Span":
        """Attach attributes discovered mid-span (e.g. the level's stats)."""
        self.attrs.update(attrs)
        return self

    def sync(self, *arrays) -> "Span":
        """Register device arrays to ``block_until_ready`` at span exit, so
        the recorded duration covers device time, not just dispatch time.
        The wait runs in a ``sync`` child span and counts one host sync."""
        self._sync = self._sync + tuple(arrays)
        return self


class _NullSpan:
    """The disabled-tracing span: every method is attribute lookup + pass.
    ``sync`` intentionally does NOT block — a disabled tracer must not
    change the run's async dispatch behaviour."""

    __slots__ = ()

    def set(self, **attrs):
        return self

    def sync(self, *arrays):
        return self


NULL_SPAN = _NullSpan()


class _NullCtx:
    """Zero-allocation context manager yielding the shared no-op span."""

    __slots__ = ()

    def __enter__(self):
        return NULL_SPAN

    def __exit__(self, *exc):
        return False


NULL_CTX = _NullCtx()


class Tracer:
    """Collects a run's spans (completion order) and counts, and optionally
    streams each finished span to a :class:`repro.obs.journal.Journal`."""

    def __init__(self, name: str = "run", *, clock=None, enabled: bool = True,
                 journal=None, profiler: bool = False):
        self.name = name
        self.clock = clock or MonotonicClock()
        self.enabled = bool(enabled)
        self.journal = journal
        self.profiler = bool(profiler)
        self.spans: list[Span] = []
        self._stack: list[Span] = []
        self._counts: dict[str, float] = {}

    def span(self, name: str, **attrs):
        """Context manager of one nested span; the shared no-op context
        when the tracer is disabled."""
        if not self.enabled:
            return NULL_CTX
        return self._span(name, attrs)

    @contextmanager
    def _span(self, name: str, attrs: dict):
        parent = self._stack[-1] if self._stack else None
        path = f"{parent.path}/{name}" if parent is not None else name
        ann = None
        if self.profiler:
            import jax.profiler

            ann = jax.profiler.TraceAnnotation(path)
            ann.__enter__()
        sp = Span(name=name, path=path, depth=len(self._stack),
                  t0=self.clock.now(), attrs=dict(attrs))
        self._stack.append(sp)
        try:
            yield sp
        except BaseException as e:
            sp.attrs.setdefault("error", type(e).__name__)
            raise
        finally:
            try:
                if sp._sync:
                    self._block(sp)
                sp.t1 = self.clock.now()
                self._stack.pop()
                self.spans.append(sp)
                if self.journal is not None:
                    self.journal.span(sp)
            finally:
                if ann is not None:
                    ann.__exit__(None, None, None)

    def _block(self, sp: Span):
        """Wait for the arrays ``sp.sync`` registered, in a ``sync`` child
        span, counted as one host sync."""
        import jax

        with self.span("sync", site=sp.name):
            for a in sp._sync:
                jax.block_until_ready(a)
        self.count(SYNC_COUNT, site=sp.name)

    @contextmanager
    def activate(self):
        """Make this the tracer :func:`current` returns while the block
        runs (drivers open it around their ``total`` span)."""
        token = _CURRENT.set(self)
        try:
            yield self
        finally:
            _CURRENT.reset(token)

    def count(self, name: str, n: float = 1, **labels):
        """Add ``n`` to the run's count ``name``; when obs is enabled the
        registry's ``pc_<name>_total{labels}`` gets the same increment."""
        if self.enabled:
            self._counts[name] = self._counts.get(name, 0) + n
        if enabled():
            metrics.get_registry().inc(f"pc_{name}_total", n, **labels)

    def counts(self) -> dict:
        """The run's counts by name, summed over labels (``PCRun.counts``)."""
        return dict(self._counts)

    # -- derived views -------------------------------------------------------
    def timings(self) -> dict:
        """The classic ``timings_s`` dict: span durations keyed by NAME
        (repeated names sum — e.g. multi-launch phases), insertion-ordered
        by first completion. This is what ``PCRun.timings_s`` now is."""
        out: dict[str, float] = {}
        for sp in self.spans:
            if sp.t1 is None:
                continue
            out[sp.name] = out.get(sp.name, 0.0) + sp.dur_s
        return out

    def finish(self, **attrs):
        """Write the closing ``run`` record (timings + caller attrs) and
        release the journal. No-op without a journal."""
        if self.journal is not None:
            self.journal.record("run", name=self.name,
                                ts=self.clock.now(),
                                timings_s=self.timings(),
                                counts=self.counts(), attrs=attrs)
            self.journal.close()


_CURRENT: contextvars.ContextVar = contextvars.ContextVar("repro_obs_tracer",
                                                         default=None)
#: what :func:`current` returns with no tracer open: spans are no-ops and
#: counts reach only the registry (when obs is enabled)
NULL_TRACER = Tracer("none", enabled=False)


def current() -> Tracer:
    """The tracer the enclosing driver activated, else :data:`NULL_TRACER`."""
    return _CURRENT.get() or NULL_TRACER


def fetch(x, *, site: str):
    """``jax.device_get(x)`` as a counted blocking read: inside a ``sync``
    span (attribute ``site``) of the current tracer, plus one
    ``host_syncs`` count labeled ``site``. Returns what device_get returns."""
    import jax

    tr = current()
    with tr.span("sync", site=site):
        out = jax.device_get(x)
    tr.count(SYNC_COUNT, site=site)
    return out
