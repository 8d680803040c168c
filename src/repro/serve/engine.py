"""Batched serving engine: slot-based batching over the decode step with
a static KV budget.

``serve_step`` is the unit the dry-run lowers (one token for the whole
batch against a seq_len cache).  The engine adds simple continuous
batching on top: finished sequences release their slot and queued
requests claim it.  The cache holds one position for all rows
(``lm.init_cache``) and a claimed row is not reset, so only requests
admitted together are served as a fresh cache would serve them.

Admission is host-only bookkeeping: it places queued requests in free
slots and touches no device buffer.  Prompts reach the device through
``run``'s host-built token batch, one position per step (prefill by
decode), in the same ``step_fn`` call that decodes the other rows.

The engine's step (``greedy_step`` around ``make_serve_step``) takes the
argmax on the device: a decoding row feeds the previous step's token,
which never leaves the device, so the host can dispatch step k before it
reads step k-1.  A request ends only at ``max_new``, so which request
holds which row at which step, and whether the row feeds a prompt token
or its own last output, is fixed at admission: ``run`` frees a row once
its last step is dispatched (and admits into it before the next step),
not when that step is read.  Each ``run`` iteration builds the feed of
step k, dispatches step k, then reads step k-1's tokens and appends them
to their requests; the step left in flight is read by the next
iteration, or the next ``run`` call.  Where the step just dispatched is
the last the current work needs (no row has a further step and the
queue is empty) it is read in the same iteration, so ``run`` returns
every finished request with nothing left in flight.

Instruments (``repro.obs``; always on, a few µs a step).  Spans, which
reach the JAX profiler's trace whenever it records: ``engine.admit``
(args ``requests``, ``prompt_tokens``, ``rids``) when a request is
admitted, and ``engine.step`` (``step``, ``rows``) with its children
``engine.feed`` (the token batch built on the host), ``engine.dispatch``
(the ``step_fn`` call) and ``engine.sample`` (arg ``step``, the step it
reads: the host read of that step's tokens, where the host waits for the
device), one per step.  Histograms of ordered samples:
``engine.admit_s`` per admission, ``engine.queue_wait_s`` per request
(submit to the end of its own admission), ``engine.host_gap_s`` per step
after the engine's first (end of the engine's previous iteration, its
read or, where it read nothing, its dispatch, to this dispatch, less the
admission time between them), ``engine.useful_rows`` per step (rows that
appended an output token) and ``engine.ttft_s`` per request (submit to
the host's read of its first token).  Counters: ``engine.steps``,
``engine.steps_ahead`` (the steps dispatched while the previous step's
tokens were still unread: ``engine.steps`` less one for each time the
engine ran out of work), ``engine.requests_admitted``,
``engine.prompt_tokens_admitted``, ``engine.tokens_out``,
``engine.rows_prefill`` and ``engine.cache_donations``, the steps that
consumed the cache they were given and so wrote its new positions in
place (read on the host from one leaf's ``is_deleted()``; the step
donates the cache, so the count equals ``engine.steps`` wherever the
backend can donate).  Each request keeps its own times
(``t_submit``, ``t_admit``, ``t_first``; perf_counter) under its ``rid``.
For a model with MoE layers the step also counts, on the device, the
tokens of all rows routed to each held expert of each MoE layer; the
host reads them with the step's tokens, in one read, into the histogram
``moe.expert_tokens`` (their mean, tokens per held expert per layer, one
sample per step) and the counter ``moe.routed_tokens`` (their sum).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import lm
from repro.models.common import AxisRules, RuntimeCfg
from repro.obs import metrics, runtime_hooks, span, timed


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                       # [Tp] int32
    max_new: int = 16
    out: list = field(default_factory=list)
    done: bool = False
    t_submit: Optional[float] = None         # perf_counter times
    t_admit: Optional[float] = None
    t_first: Optional[float] = None


def make_serve_step(spec, rt: RuntimeCfg, rules: Optional[AxisRules] = None,
                    *, routed: bool = False):
    """(params, cache, tokens) -> (logits, cache), and with ``routed``
    the tokens routed to each held expert (``lm.decode_step``)."""
    def serve_step(params, cache, tokens):
        return lm.decode_step(params, cache, tokens, spec, rt, rules,
                              routed=routed)
    return serve_step


def greedy_step(step):
    """Greedy sampling inside the step: (params, cache, host, prev) ->
    (tokens, cache, ...) around ``step`` ((params, cache, tokens [B, 1])
    -> (logits, cache, ...)).  Row i feeds ``host[i]``, or ``prev[i]``
    (the previous step's token, left on the device) where ``host[i]`` is
    -1, and gets the argmax of its logits, all as int32 [B]."""
    def serve_step(params, cache, host, prev):
        tokens = jnp.where(host >= 0, host, prev)[:, None]
        logits, cache, *rest = step(params, cache, tokens)
        return (jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32), cache,
                *rest)
    return serve_step


class Engine:
    """Slot-based continuous batching over ``serve_step``."""

    def __init__(self, spec, rt: RuntimeCfg, params, *, batch_slots: int,
                 kv_len: int, rules: Optional[AxisRules] = None):
        self.spec, self.rt, self.params = spec, rt, params
        self.kv_len = kv_len
        self.slots: list[Optional[Request]] = [None] * batch_slots
        self.cache = lm.init_cache(spec, rt, batch_slots, kv_len)
        self.routed = spec.moe is not None
        self.serve_step = greedy_step(make_serve_step(spec, rt, rules,
                                                      routed=self.routed))
        # the step consumes the cache it is given and writes the new
        # positions into it in place (``lm.decode_step``)
        self.step_fn = jax.jit(self.serve_step, donate_argnums=(1,))
        self.queue: list[Request] = []
        self.n_steps = 0
        self._tokens = jnp.zeros((batch_slots,), jnp.int32)  # last argmax
        self._pending = None        # the dispatched step not yet read
        self._gap_from: Optional[float] = None  # last read, or dispatch
        self._admit_s = 0.0                     # admission time since
        runtime_hooks()

    def submit(self, req: Request):
        req.t_submit = time.perf_counter()
        self.queue.append(req)

    def _admit(self):
        """Move queued requests into free slots (host only: no device
        work).  ``run`` then feeds each prompt one position per step."""
        if not self.queue:
            return
        take = self.queue[:sum(s is None for s in self.slots)]
        if not take:
            return
        n_tok = sum(len(r.prompt) for r in take)
        with timed("engine.admit", requests=len(take), prompt_tokens=n_tok,
                   rids=[r.rid for r in take]) as adm:
            for i, s in enumerate(self.slots):
                if s is None and self.queue:
                    req = self.queue.pop(0)
                    self.slots[i] = req
                    req._fed = req._due = 0
                    req.t_admit = time.perf_counter()
                    metrics.histogram("engine.queue_wait_s").observe(
                        req.t_admit - req.t_submit)
        self._admit_s += adm.dur
        metrics.histogram("engine.admit_s").observe(adm.dur)
        metrics.counter("engine.requests_admitted").inc(len(take))
        metrics.counter("engine.prompt_tokens_admitted").inc(n_tok)

    def _busy(self) -> bool:
        """A row has a step to come, or a request waits for a row."""
        return bool(self.queue) or any(s is not None for s in self.slots)

    def _read(self, step: int, tokens, routed: list, rows: list
              ) -> list[Request]:
        """Read a dispatched step's tokens (and routed counts) and append
        them to its rows' requests; returns the requests it finished."""
        with span("engine.sample", step=step):
            tokens, routed = jax.device_get((tokens, routed))
        self._gap_from = t_read = time.perf_counter()
        if routed:
            metrics.histogram("moe.expert_tokens").observe(
                float(routed[0].mean()))
            metrics.counter("moe.routed_tokens").inc(int(routed[0].sum()))
        finished, useful = [], 0
        for i, req, emits, last in rows:
            if emits:
                req.out.append(int(tokens[i]))
                useful += 1
                if len(req.out) == 1:
                    req.t_first = t_read
                    metrics.histogram("engine.ttft_s").observe(
                        req.t_first - req.t_submit)
            if last:
                req.done = True
                finished.append(req)
        metrics.counter("engine.tokens_out").inc(useful)
        metrics.histogram("engine.useful_rows").observe(useful)
        return finished

    def run(self, max_steps: int = 64) -> list[Request]:
        """Greedy-decode all queued requests; returns finished requests."""
        finished: list[Request] = []
        self._admit()
        for _ in range(max_steps):
            if not self._busy():
                break
            with span("engine.step", step=self.n_steps) as st:
                with span("engine.feed"):
                    # the batched token: prompts feed first, then -1 marks
                    # a row that feeds its own last output (on the device);
                    # a row whose last step this is is freed
                    host = np.zeros(len(self.slots), np.int32)
                    rows, prefill = [], 0
                    for i, req in enumerate(self.slots):
                        if req is None:
                            continue
                        if req._fed < len(req.prompt):
                            host[i] = req.prompt[req._fed]
                            req._fed += 1
                            prefill += 1
                        elif req._due:
                            host[i] = -1
                        emits = req._fed >= len(req.prompt)
                        req._due += emits
                        last = req._due >= req.max_new
                        rows.append((i, req, emits, last))
                        if last:
                            self.slots[i] = None
                st.set(rows=len(rows))
                t_dispatch = time.perf_counter()
                with span("engine.dispatch"):
                    given = jax.tree.leaves(self.cache)[0]
                    self._tokens, self.cache, *routed = self.step_fn(
                        self.params, self.cache, host, self._tokens)
                donated = given.is_deleted()
                ahead, self._pending = self._pending, (
                    self.n_steps, self._tokens, routed, rows)
                if self._gap_from is not None:
                    metrics.histogram("engine.host_gap_s").observe(
                        t_dispatch - self._gap_from - self._admit_s)
                self._gap_from, self._admit_s = time.perf_counter(), 0.0
                self.n_steps += 1
                metrics.counter("engine.steps").inc()
                metrics.counter("engine.steps_ahead").inc(
                    int(ahead is not None))
                metrics.counter("engine.cache_donations").inc(int(donated))
                metrics.counter("engine.rows_prefill").inc(prefill)
                if ahead is not None:
                    finished += self._read(*ahead)
                if not self._busy():          # the last step this work needs
                    finished += self._read(*self._pending)
                    self._pending = None
            self._admit()
        return finished
