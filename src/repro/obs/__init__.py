"""Observability layer: simulated-execution timelines, self-profiling
spans, and pipeline metrics.

Three coupled pieces (see each module's docstring):

* :mod:`repro.obs.timeline` — Perfetto/Chrome-trace export of the
  *modeled* execution (schedule replay slots, compute/comm streams,
  collectives, resilience epochs, serving pool lanes) plus the derived
  :class:`~repro.obs.timeline.UtilizationReport`.  Reached through
  ``Trace.timeline(...)`` / ``Job.timeline(...)``.
* :mod:`repro.obs.spans` — self-profiling tracer for the generator
  and the runtime (``REPRO_TRACE=1`` or :func:`profiled`; bridged into
  the JAX profiler's trace whenever that records), same export format;
  :func:`runtime_hooks` adds garbage-collection and compile instruments.
* :mod:`repro.obs.metrics` — counters/gauges/histograms (each keeping
  its newest samples in order) + :func:`snapshot`/:func:`diff`,
  surfaced by ``python -m repro.obs``.

``spans``/``metrics``/``log`` are stdlib-only and import eagerly;
``timeline`` depends on the core simulation layer and loads lazily so
``repro.core`` modules can import ``repro.obs`` without a cycle.
"""
from __future__ import annotations

from .log import configure as configure_logging
from .log import get_logger
from .metrics import (REGISTRY, counter, diff, gauge, histogram, snapshot)
from .spans import (Profile, enabled, profiled, runtime_hooks, span,
                    take_events, timed, traced)

__all__ = [
    "configure_logging", "get_logger",
    "REGISTRY", "counter", "gauge", "histogram", "snapshot", "diff",
    "span", "timed", "traced", "profiled", "enabled", "take_events",
    "Profile", "runtime_hooks",
    # lazy (from .timeline):
    "Timeline", "TimelineEvent", "UtilizationReport",
    "build_timeline", "job_timeline", "profile_chrome_trace",
    "validate_chrome_trace",
]

_TIMELINE_NAMES = {"Timeline", "TimelineEvent", "UtilizationReport",
                   "build_timeline", "job_timeline",
                   "profile_chrome_trace", "validate_chrome_trace"}


def __getattr__(name: str):
    if name in _TIMELINE_NAMES:
        from . import timeline as _tl
        return getattr(_tl, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
