"""Self-profiling spans for the generator pipeline and the runtime
(observability tentpole, piece 2).

A contextvar-scoped tracer with near-zero overhead when off: the hot
pipeline stages (assemble, distribute/lower, instantiate, simulate,
batched kernel dispatch, Chakra export, DSE sweeps), the serve engine's
admission and decode steps and the train launcher's steps are wrapped in
``with span("stage", attr=...):`` blocks.  Off — the default — ``span()``
is two checks returning a shared no-op context manager; no allocation,
no clock read (guarded ≤2 % of the batched sweep in
``benchmarks/perf_smoke.py``).

Two sinks, each switched on from outside the code it traces:

* the profiler bridge: whenever a JAX profiler session is recording
  (``jax.profiler.trace``/``start_trace``, or a profiler server), every
  span is also a ``jax.profiler.TraceAnnotation`` carrying its args, so
  the program's spans share the device trace's clock and name its idle
  gaps.  jax is never imported here: the check is resolved once the
  process has imported it.
* the in-process recorder: ``REPRO_TRACE=1`` in the environment
  (process-lifetime recording — call :func:`take_events` /
  :func:`export` to harvest) or scoped with::

    with repro.obs.profiled() as prof:
        Scenario(spec).train(batch=64, seq=512).sweep(64)
    prof.summary()          # per-span-name total/self times
    prof.export("sweep_profile.json")   # Perfetto / chrome://tracing

Span records carry wall-clock ``ts``/``dur`` (perf_counter), thread id,
their own ``id`` and their ``parent``'s (the enclosing span, from a
contextvar, so concurrent sweep workers nest correctly), nesting depth,
and free-form ``args``; export shares the Chrome-trace JSON emitter with
the simulated-execution timelines (:mod:`repro.obs.timeline`).

:func:`timed` is a span that always reads the clock, for callers that
need the duration with tracing off (the launcher's step time), and
:func:`runtime_hooks` makes the host's own pauses visible: garbage
collection as ``py.gc`` spans and the ``py.gc_s`` histogram, XLA
compiles as ``jit.compiles`` / ``jit.compile_s``.
"""
from __future__ import annotations

import contextvars
import functools
import gc
import itertools
import os
import sys
import threading
import time
from dataclasses import dataclass, field

from . import metrics as _metrics

__all__ = ["span", "timed", "traced", "enabled", "enable", "disable",
           "profiled", "take_events", "export", "Profile", "SpanEvent",
           "runtime_hooks"]

_enabled = False                      # module-global fast-path check
_events: list = []                    # finished SpanEvent records
# re-entrant: a garbage collection, and so a ``py.gc`` span, can start
# inside any allocation, also one made while the lock is held
_lock = threading.RLock()
# (id, depth) of the innermost open span; id 0 is "no span"
_current: contextvars.ContextVar = contextvars.ContextVar(
    "repro_obs_span", default=(0, 0))
_next_id = itertools.count(1).__next__


@dataclass(frozen=True)
class SpanEvent:
    """One finished span (times in seconds on the perf_counter clock);
    ``parent`` is the ``id`` of the span it ran inside, 0 at the top."""
    name: str
    ts: float
    dur: float
    tid: int
    depth: int
    args: dict = field(default_factory=dict)
    id: int = 0
    parent: int = 0


def _profiler_off() -> bool:
    return False


def _profiler_on() -> bool:
    """Whether a JAX profiler session is recording.  Until jax has been
    imported none can be; after that the check resolves, once, to
    jaxlib's ``TraceMe.is_enabled``."""
    global _profiler_on, _Annotation
    if "jax" not in sys.modules:
        return False
    try:
        from jax._src.lib import _profiler
        from jax.profiler import TraceAnnotation
    except ImportError:              # a jax without the profiler
        _profiler_on = _profiler_off
        return False

    class _Annotation(TraceAnnotation):
        """A profiler annotation with :meth:`_Span.set`'s interface."""

        def set(self, **kw) -> "_Annotation":
            self.set_metadata(**kw)
            return self

    _profiler_on = _profiler.TraceMe.is_enabled
    return _profiler_on()


_Annotation = None                     # bound by _profiler_on with jax


class _Noop:
    """Shared do-nothing context manager: the disabled fast path."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **kw) -> "_Noop":          # parity with _Span.set
        return self


_NOOP = _Noop()


class _Span:
    __slots__ = ("name", "args", "_t0", "_tok", "_id", "_parent",
                 "_depth", "_ann")

    def __init__(self, name: str, args: dict):
        self.name = name
        self.args = args
        self._ann = None

    def set(self, **kw) -> "_Span":
        """Attach attributes discovered mid-span (result sizes etc.)."""
        self.args.update(kw)
        if self._ann is not None:
            self._ann.set_metadata(**kw)
        return self

    def __enter__(self) -> "_Span":
        self._parent, self._depth = _current.get()
        self._id = _next_id()
        self._tok = _current.set((self._id, self._depth + 1))
        if _profiler_on():
            self._ann = _Annotation(self.name, **self.args)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        _current.reset(self._tok)
        ev = SpanEvent(name=self.name, ts=self._t0, dur=dur,
                       tid=threading.get_ident(), depth=self._depth,
                       args=self.args, id=self._id, parent=self._parent)
        with _lock:
            _events.append(ev)
        return False


def span(name: str, **args):
    """A profiling span context manager: recorded while tracing is on,
    a ``TraceAnnotation`` while a JAX profiler records, and otherwise a
    shared no-op (the common case — keep call sites unconditional)."""
    if _enabled:
        return _Span(name, args)
    if _profiler_on():
        return _Annotation(name, **args)
    return _NOOP


class _Timed:
    """:func:`timed`'s context manager: a span plus its own clock."""
    __slots__ = ("_span", "t0", "t1")

    def __init__(self, sp):
        self._span = sp

    def set(self, **kw) -> "_Timed":
        self._span.set(**kw)
        return self

    def __enter__(self) -> "_Timed":
        self._span.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.t1 = time.perf_counter()
        return self._span.__exit__(*exc)

    @property
    def dur(self) -> float:
        return self.t1 - self.t0


def timed(name: str, **args) -> _Timed:
    """:func:`span` that also reads the clock whether or not anything
    traces: ``.t0``/``.t1`` (perf_counter) and ``.dur`` after the block."""
    return _Timed(span(name, **args))


def traced(name: str | None = None, **args):
    """Decorator form: ``@traced("dse.sweep")`` wraps the call in a
    span (name defaults to the function's qualified name)."""
    def deco(fn):
        label = name or fn.__qualname__

        @functools.wraps(fn)
        def wrapper(*a, **kw):
            if not _enabled and not _profiler_on():
                return fn(*a, **kw)
            with span(label, **args):
                return fn(*a, **kw)
        return wrapper
    return deco


def enabled() -> bool:
    return _enabled


def enable() -> None:
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def take_events(clear: bool = True) -> list:
    """Snapshot (and by default drain) the recorded spans."""
    with _lock:
        out = list(_events)
        if clear:
            _events.clear()
    return out


class Profile:
    """Harvested spans from one :func:`profiled` block."""

    def __init__(self, events: list):
        self.events: list[SpanEvent] = events

    def totals(self) -> dict:
        """Per-name aggregate: {name: {"count", "total_s", "self_s"}}.

        ``self_s`` subtracts the time spent in the spans whose parent it
        is, so exclusive costs are attributable."""
        out: dict[str, dict] = {}
        for e in self.events:
            rec = out.setdefault(e.name, {"count": 0, "total_s": 0.0,
                                          "self_s": 0.0})
            rec["count"] += 1
            rec["total_s"] += e.dur
            rec["self_s"] += e.dur
        # children charge their duration back to the span they ran in
        by_id = {e.id: e for e in self.events}
        for e in self.events:
            p = by_id.get(e.parent)
            if p is not None:
                out[p.name]["self_s"] -= e.dur
        return out

    def summary(self) -> str:
        rows = sorted(self.totals().items(),
                      key=lambda kv: -kv[1]["total_s"])
        lines = [f"{'span':<32} {'count':>7} {'total_ms':>10} {'self_ms':>10}"]
        for name, rec in rows:
            lines.append(f"{name:<32} {rec['count']:>7} "
                         f"{rec['total_s'] * 1e3:>10.2f} "
                         f"{max(0.0, rec['self_s']) * 1e3:>10.2f}")
        return "\n".join(lines)

    def chrome_trace(self) -> dict:
        """Chrome-trace JSON dict (see :func:`repro.obs.timeline.
        chrome_trace_events` for the schema conventions shared with the
        simulated-execution timelines)."""
        from .timeline import profile_chrome_trace
        return profile_chrome_trace(self.events)

    def export(self, path: str) -> str:
        import json
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path


class _Profiled:
    """Context manager flipping the tracer on for a scoped block."""

    def __init__(self):
        self.profile = Profile([])

    def __enter__(self) -> Profile:
        self._was = _enabled
        self._mark = len(_events)
        enable()
        return self.profile

    def __exit__(self, *exc):
        global _enabled
        _enabled = self._was
        with _lock:
            self.profile.events = _events[self._mark:]
            del _events[self._mark:]
        return False


def profiled() -> _Profiled:
    """``with repro.obs.profiled() as prof:`` — scoped tracing; the
    yielded :class:`Profile` fills when the block exits."""
    return _Profiled()


def export(path: str, *, clear: bool = True) -> str:
    """Export everything recorded so far (the ``REPRO_TRACE=1`` path)."""
    prof = Profile(take_events(clear=clear))
    return prof.export(path)


if os.environ.get("REPRO_TRACE", "").strip() not in ("", "0", "false",
                                                     "off"):
    enable()


# --------------------------------------------------------------------------
# Process hooks: the host's own pauses
# --------------------------------------------------------------------------

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_hooked = False
_gc_open = None                  # the collection in progress (one at a time)


def _on_gc(phase: str, info: dict) -> None:
    global _gc_open
    if phase == "start":
        _gc_open = timed("py.gc", generation=info["generation"])
        _gc_open.__enter__()
    elif _gc_open is not None:
        t, _gc_open = _gc_open, None
        t.set(collected=info["collected"])
        t.__exit__(None, None, None)
        _metrics.histogram("py.gc_s").observe(t.dur)


def _on_jax_duration(event: str, duration: float, **_) -> None:
    if event == COMPILE_EVENT:
        _metrics.counter("jit.compiles").inc()
        _metrics.histogram("jit.compile_s").observe(duration)


def runtime_hooks() -> None:
    """Install, once per process, the hooks that make host pauses
    visible: ``gc.callbacks`` that wrap each collection in a ``py.gc``
    span (args ``generation``, ``collected``) and feed its seconds to the
    ``py.gc_s`` histogram, and, where jax is importable, a
    ``jax.monitoring`` listener that counts XLA backend compiles
    (``jit.compiles``) and feeds their seconds to ``jit.compile_s``."""
    global _hooked
    with _lock:
        if _hooked:
            return
        _hooked = True
    gc.callbacks.append(_on_gc)
    try:
        import jax.monitoring
    except ImportError:
        return
    jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)
