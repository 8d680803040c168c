"""Median host gap between decode steps in the window, in ms: the
engine's ``engine.host_gap_s`` histogram (``repro.obs``), from the end of
one step's sample to the next step's dispatch less the admission between
them, over its newest samples, one per step of the window (set-up's
warm-up steps come before them).  Nothing to read where the program
keeps no such samples."""
import statistics


def read(r):
    from repro.obs import metrics
    n = sum(r.window["steps_per_wave"])
    newest = getattr(metrics.histogram("engine.host_gap_s"), "newest", None)
    xs = newest(n) if newest is not None else []
    if not n or len(xs) < n:
        return None
    return statistics.median(xs) * 1e3
