"""Serving: the jax runtime engine (continuous batching) and the
symbolic phase-program front door.

The runtime half (:class:`Engine`) executes real decode steps under
jax; the symbolic half (:class:`repro.api.Job` /
:class:`repro.core.serving.JobResult`) predicts the same request
timeline — TTFT / TPOT / tokens/s / KV footprint — in closed form,
so capacity planning never needs a device:

    from repro.serve import Job
    job = Scenario(spec).prefill(batch=8, seq=1024).parallel(tp=8) \\
        .generation(out_tokens=512)
    job.evaluate(H100_HGX).describe()
"""
from repro.api import Job, Phase
from repro.core.serving import DecodeSeries, JobResult, PhaseResult

from .engine import Engine, Request, greedy_step, make_serve_step

__all__ = ["Engine", "Request", "greedy_step", "make_serve_step",
           "Job", "Phase", "JobResult", "PhaseResult", "DecodeSeries"]
