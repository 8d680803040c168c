"""Seconds of backend compilation during set-up (``jax.monitoring``'s
``backend_compile_duration``): what the persistent cache did not hold."""


def read(r):
    return r.compile_s
