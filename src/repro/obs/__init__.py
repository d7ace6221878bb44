"""repro.obs — unified observability: spans, metrics, journals.

Three pieces, one gate:

* :mod:`~repro.obs.trace` — nestable trace spans with injectable clocks,
  device-time-aware `sync`, optional jax-profiler annotation; `fetch`,
  the one counted seam for blocking device reads; `current()`, the tracer
  the enclosing driver opened.
* :mod:`~repro.obs.metrics` — labeled counter/gauge/histogram registry +
  Prometheus text exposition; `record_level_stats` is the one shared
  definition of the dispatch/gather counters.
* :mod:`~repro.obs.journal` — JSONL run journals, deterministic under a
  virtual clock.

The split that keeps results bit-identical: driver-local *tracers* are
always on (they ARE the `timings_s` plumbing the drivers already paid
for), while anything with a side effect beyond a float — journal files,
the global registry, profiler annotation — is off unless
`obs.configure(enabled=True, ...)` / ``REPRO_OBS=1`` says otherwise.
"""
from __future__ import annotations

from .config import (ObsConfig, configure, disable, enable, enabled,
                     get_config, scoped)
from .journal import SCHEMA_VERSION, Journal, phase_summary, read_journal
from .metrics import (CHUNKS, COL_GATHER_BYTES, COL_GATHERS, DISPATCHES,
                      HOST_SYNCS, LEVELS, TESTS_TOTAL, MetricsRegistry,
                      get_registry, record_level_stats, scoped_registry)
from .trace import (NULL_CTX, NULL_SPAN, NULL_TRACER, ManualClock,
                    MonotonicClock, Span, Tracer, current, fetch)

__all__ = [
    "ObsConfig", "configure", "enable", "disable", "enabled", "get_config",
    "scoped", "Journal", "read_journal", "phase_summary", "SCHEMA_VERSION",
    "MetricsRegistry", "get_registry", "scoped_registry", "record_level_stats",
    "DISPATCHES", "CHUNKS", "COL_GATHERS", "COL_GATHER_BYTES", "LEVELS",
    "TESTS_TOTAL", "HOST_SYNCS", "ManualClock", "MonotonicClock", "Span",
    "Tracer", "NULL_SPAN", "NULL_CTX", "NULL_TRACER", "span", "journal_for",
    "run_tracer", "current", "fetch",
]


def journal_for(path: str | None = None) -> Journal | None:
    """A Journal for the configured (or given) path, or None. Only returns
    a journal when obs is enabled — the zero-overhead contract."""
    cfg = get_config()
    if not cfg.enabled:
        return None
    p = path or cfg.journal_path
    return Journal(p) if p else None


def run_tracer(name: str, *, clock=None, journal_path: str | None = None) -> Tracer:
    """The driver entry point: an always-enabled tracer (it replaces the
    drivers' perf_counter plumbing, so `timings_s` stays populated) whose
    journal / profiler hand-off only engage when obs is configured on."""
    cfg = get_config()
    return Tracer(
        name,
        clock=clock or cfg.clock,
        enabled=True,
        journal=journal_for(journal_path),
        profiler=cfg.enabled and cfg.jax_profiler,
    )


def span(name: str, **attrs):
    """Module-level ad-hoc span on a global tracer — for call sites with no
    driver tracer in reach (e.g. `pc_scan_batch`). A no-op context when obs
    is disabled."""
    if not enabled():
        return NULL_CTX
    return _global_tracer().span(name, **attrs)


_TRACER: Tracer | None = None


def _global_tracer() -> Tracer:
    global _TRACER
    cfg = get_config()
    if _TRACER is None or (_TRACER.journal.path if _TRACER.journal else None) \
            != cfg.journal_path:
        _TRACER = Tracer("global", clock=cfg.clock,
                         journal=journal_for(), profiler=cfg.jax_profiler)
    return _TRACER
