"""Median time of one admission in the window, in ms: the engine's
``engine.admit_s`` histogram (``repro.obs``), one sample for each
admission that admitted a request, over its newest samples, one per wave
of the window (set-up's warm-up wave comes before them).  Nothing to
read where the program keeps no such samples."""
import statistics


def read(r):
    from repro.obs import metrics
    n = r.window["waves"]
    newest = getattr(metrics.histogram("engine.admit_s"), "newest", None)
    xs = newest(n) if newest is not None else []
    if not n or len(xs) < n:
        return None
    return statistics.median(xs) * 1e3
