"""Shared pieces of the benchmark: the run context, the compile clock,
the traced window, and the lookup of traffic loops and metric readers by name."""
from __future__ import annotations

import importlib.util
import json
import shutil
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

BENCH = Path(__file__).resolve().parent


@dataclass
class Context:
    name: str                  # cell name
    seed: int
    config: dict               # configs/<config>.json
    traffic: dict              # traffic/<traffic>.json
    limits: dict               # limits/<cell>.json


@dataclass
class Reading:
    """What a per-layer metric reader may read."""
    ctx: Context
    window: dict               # the loop's window record
    trace: dict                # trace.reduce() of the traced window
    compile_s: float           # backend compile seconds during set-up
    devs: list = field(default_factory=list)

    def peak(self) -> dict:
        return peaks(self.devs[0].device_kind)


class CompileClock:
    """Seconds jax spends in backend compilation (persistent-cache hits
    skip it)."""

    def __init__(self):
        import jax
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += duration


def runtime_spec(config: dict):
    """The ``ModelSpec`` the runtime cells run: the configuration's
    ``spec`` at its ``runtime_layers``."""
    from repro.core import ModelSpec
    return ModelSpec(**{**config["spec"], "n_layers": config["runtime_layers"]})


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; a device that
    is not in ``peaks.json`` is an error, never a default."""
    table = json.loads((BENCH / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"perfbench/peaks.json")
    return table[device_kind]


def _load(path: Path, what: str):
    if not path.is_file():
        raise FileNotFoundError(f"no {what} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{what}_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_loop(name: str):
    return _load(BENCH / "loops" / f"{name}.py", "loop")


def load_reader(metric: str):
    """``metrics/<metric>.py``'s ``read(reading)``: a number, or None
    where the run has nothing for it to read."""
    return _load(BENCH / "metrics" / f"{metric}.py", "reader").read


class Window:
    """The measured window: a host clock around closed-loop units, and
    with ``trace`` a profiler trace of its first ``trace_seconds`` (all
    of it by default), reduced to busy and idle time once the window has
    closed.  Each unit and host phase runs inside ``phase(name)``, a
    ``TraceAnnotation`` that names the idle gaps."""

    def __init__(self, seconds: float, trace: bool,
                 trace_seconds: float | None = None):
        self.seconds = seconds
        self.trace = trace
        self.trace_seconds = min(seconds, trace_seconds or seconds)
        self.tracing = False
        self.stop_s = 0.0
        self.dir = None
        self.reduced = None
        self.names = {"bench.window"}
        self.t0 = self.t1 = None

    def __enter__(self) -> "Window":
        import jax
        if self.trace:
            self.dir = tempfile.mkdtemp(prefix="perfbench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # keep host overhead low
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._ann = jax.profiler.TraceAnnotation("bench.window")
            self._ann.__enter__()
            self.tracing = True
        self.t0 = time.perf_counter()
        self.deadline = self.t0 + self.seconds
        return self

    def open(self) -> bool:
        now = time.perf_counter()
        if (self.tracing and self.trace_seconds < self.seconds
                and now >= self.t0 + self.trace_seconds):
            self._stop()           # its cost is left out of the window
            self.stop_s = time.perf_counter() - now
            self.deadline += self.stop_s
        return now < self.deadline

    def phase(self, name: str):
        import jax
        self.names.add(name)
        return jax.profiler.TraceAnnotation(name)

    def _stop(self) -> None:
        import jax
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.tracing = False

    def __exit__(self, *exc):
        self.t1 = time.perf_counter()
        if self.tracing:
            self._stop()
        if self.trace:
            if exc[0] is None:
                from xplane import reduce_dir
                t = time.perf_counter()
                self.reduced = reduce_dir(self.dir, self.names)
                print(f"perfbench: trace reduced in "
                      f"{time.perf_counter() - t:.1f} s", flush=True)
            shutil.rmtree(self.dir, ignore_errors=True)
        return False

    @property
    def elapsed(self) -> float:
        return self.t1 - self.t0 - self.stop_s
