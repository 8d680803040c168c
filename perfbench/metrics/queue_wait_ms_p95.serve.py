"""95th percentile of a request's queue wait in the window, in ms: the
engine's ``engine.queue_wait_s`` histogram (``repro.obs``), submission to
the end of the request's own admission, over its newest samples, one per
request the window attempted (set-up's warm-up requests come before
them).  Nothing to read where the program keeps no such samples."""


def read(r):
    from repro.obs import metrics
    n = r.window["attempted"]
    h = metrics.histogram("engine.queue_wait_s")
    newest = getattr(h, "newest", None)
    xs = newest(n) if newest is not None else []
    if not n or len(xs) < n:
        return None
    return h.quantile(0.95, n) * 1e3
