"""Percent of the window's decode rows that produced an output token:
the sum of the engine's ``engine.useful_rows`` histogram (``repro.obs``)
over its newest samples, one per step of the window (set-up's warm-up
steps come before them), over slots times steps.  The rest were empty
or fed a prompt token.  Nothing to read where the program keeps no such
samples."""


def read(r):
    from repro.obs import metrics
    n = sum(r.window["steps_per_wave"])
    newest = getattr(metrics.histogram("engine.useful_rows"), "newest", None)
    xs = newest(n) if newest is not None else []
    if not n or len(xs) < n:
        return None
    return 100.0 * sum(xs) / (r.ctx.traffic["slots"] * n)
