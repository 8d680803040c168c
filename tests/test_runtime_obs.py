"""Runtime instruments (``repro.obs``): spans bridged into the JAX
profiler's trace, span ids and parents, ordered histogram samples and
their quantiles, the garbage-collection and compile hooks, the serve
engine's spans, histograms and counters, and the model's named scopes in
the train step's HLO."""
import gc
import glob
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get
from repro.core import ModelSpec
from repro.models import RuntimeCfg, init_params
from repro.obs import metrics, profiled, runtime_hooks, span, timed
from repro.obs import spans as obs_spans


@pytest.fixture(autouse=True)
def _clean_obs():
    obs_spans.disable()
    obs_spans.take_events()
    metrics.reset()
    yield
    obs_spans.disable()
    obs_spans.take_events()
    metrics.reset()


# --------------------------------------------------------------------------
# the profiler bridge
# --------------------------------------------------------------------------

def _host_events(trace_dir, names) -> dict:
    from jax.profiler import ProfileData
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    out[ev.name] = {"plane": plane.name, "line": line.name,
                                    "start": ev.start_ns,
                                    "end": ev.start_ns + ev.duration_ns,
                                    "stats": dict(ev.stats)}
    return out


@pytest.mark.parametrize("recorder", [False, True])
def test_span_reaches_the_profiler_trace(tmp_path, recorder):
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(8)).block_until_ready()
    assert span("test.off") is obs_spans._NOOP
    if recorder:
        obs_spans.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("test.outer"):
            with span("test.inner", rows=3, rids=[4, 5]) as sp:
                f(jnp.ones(8)).block_until_ready()
                sp.set(done=1)
    finally:
        jax.profiler.stop_trace()
        obs_spans.disable()
    # everything off again: the shared no-op, no clock read
    assert span("test.off", k=1) is obs_spans._NOOP
    ev = _host_events(tmp_path, {"test.outer", "test.inner"})
    outer, inner = ev["test.outer"], ev["test.inner"]
    assert (inner["plane"], inner["line"]) == (outer["plane"], outer["line"])
    assert outer["start"] <= inner["start"] < inner["end"] <= outer["end"]
    assert inner["stats"] == {"rows": 3, "rids": "[4, 5]", "done": 1}
    recorded = [e for e in obs_spans.take_events() if e.name == "test.inner"]
    assert len(recorded) == recorder
    if recorder:
        assert recorded[0].args == {"rows": 3, "rids": [4, 5], "done": 1}


def test_spans_record_their_parent():
    with profiled() as prof:
        with span("a"):
            with span("b"):
                with span("c"):
                    pass
            with span("d"):
                pass
    by = {e.name: e for e in prof.events}
    assert by["a"].parent == 0
    assert by["b"].parent == by["d"].parent == by["a"].id
    assert by["c"].parent == by["b"].id
    assert len({e.id for e in prof.events}) == 4
    tot = prof.totals()
    assert tot["a"]["self_s"] == pytest.approx(
        by["a"].dur - by["b"].dur - by["d"].dur, abs=1e-12)


def test_timed_reads_the_clock_with_tracing_off():
    with timed("test.t") as t:
        time.sleep(0.01)
    assert t.dur >= 0.01 and t.t1 - t.t0 == t.dur
    assert obs_spans.take_events() == []


# --------------------------------------------------------------------------
# histograms and process hooks
# --------------------------------------------------------------------------

def test_histogram_quantile_over_newest_samples():
    h = metrics.histogram("q")
    warm = [1e3] * 5
    window = [float(x) for x in (7, 3, 9, 1, 5, 2, 8, 10, 4, 6)]
    for x in warm + window:
        h.observe(x)
    assert h.newest(10) == window
    assert h.newest(100) == warm + window
    for q in (0.0, 0.25, 0.5, 0.95, 1.0):
        assert h.quantile(q, 10) == pytest.approx(np.percentile(window,
                                                                100 * q))
    assert h.quantile(1.0) == 1e3
    assert metrics.histogram("empty").quantile(0.5) is None
    assert set(metrics.snapshot(caches=False)["histograms"]["q"]) == {
        "count", "total", "mean", "min", "max", "bounds", "buckets"}


def test_histogram_keeps_a_bounded_ring():
    h = metrics.histogram("ring")
    for i in range(metrics.RING + 10):
        h.observe(float(i))
    assert h.count == metrics.RING + 10
    assert len(h.newest()) == metrics.RING
    assert h.newest(1) == [float(metrics.RING + 9)]


def test_gc_hook_spans_and_histogram():
    runtime_hooks()
    runtime_hooks()                       # idempotent
    assert gc.callbacks.count(obs_spans._on_gc) == 1
    with profiled() as prof:
        with span("test.work"):
            gc.collect()
    work, = [e for e in prof.events if e.name == "test.work"]
    pauses = [e for e in prof.events if e.name == "py.gc"]
    full = [e for e in pauses if e.args["generation"] == 2]
    assert full and "collected" in full[-1].args
    assert full[-1].parent == work.id
    assert metrics.histogram("py.gc_s").count >= len(pauses)


def test_compile_hook_counts_backend_compiles():
    runtime_hooks()
    c = float(time.time_ns() % 1_000_003)   # a program no cache holds
    jax.jit(lambda x: x * c + 1.0)(jnp.ones(3)).block_until_ready()
    assert metrics.counter("jit.compiles").value >= 1
    assert metrics.histogram("jit.compile_s").total > 0


# --------------------------------------------------------------------------
# the serve engine
# --------------------------------------------------------------------------

def test_engine_instruments_two_slots():
    from repro.serve import Engine, Request
    spec = ModelSpec(name="m", n_layers=2, d_model=64, n_heads=4,
                     n_kv_heads=2, d_ff=128, vocab=256)
    rt = RuntimeCfg(attention_impl="naive")
    eng = Engine(spec, rt, init_params(spec, rt, jax.random.PRNGKey(0)),
                 batch_slots=2, kv_len=64)
    reqs = [Request(rid=i, prompt=np.array([1, 2, 3 + i]), max_new=4)
            for i in range(3)]
    for r in reqs:
        eng.submit(r)
    with profiled() as prof:
        done = eng.run(max_steps=40)
    assert len(done) == 3
    steps = eng.n_steps
    h, c = metrics.histogram, metrics.counter
    assert h("engine.host_gap_s").count == steps - 1
    assert min(h("engine.host_gap_s").newest()) >= 0
    assert h("engine.useful_rows").count == c("engine.steps").value == steps
    tokens = sum(len(r.out) for r in reqs)
    assert sum(h("engine.useful_rows").newest()) == tokens
    assert c("engine.tokens_out").value == tokens
    assert h("engine.queue_wait_s").count == h("engine.ttft_s").count == 3
    assert h("engine.admit_s").count == 2      # two at once, then one
    assert c("engine.requests_admitted").value == 3
    assert c("engine.prompt_tokens_admitted").value == 9
    assert c("engine.rows_prefill").value == 9
    assert c("engine.cache_donations").value == steps   # the CPU donates
    # each step after the first is dispatched before its predecessor is read
    assert c("engine.steps_ahead").value == steps - 1
    assert all(r.t_submit <= r.t_admit <= r.t_first for r in reqs)
    # each step's phases are its children; admissions carry their rids
    step_ids = {e.id for e in prof.events if e.name == "engine.step"}
    assert len(step_ids) == steps
    for name in ("engine.feed", "engine.dispatch", "engine.sample"):
        kids = [e for e in prof.events if e.name == name]
        assert len(kids) == steps and all(k.parent in step_ids for k in kids)
    # each step is read once, in order, inside the step after it (the last
    # inside its own step)
    reads = [e for e in prof.events if e.name == "engine.sample"]
    by_id = {e.id: e.args["step"] for e in prof.events
             if e.name == "engine.step"}
    assert [e.args["step"] for e in reads] == list(range(steps))
    assert [by_id[e.parent] for e in reads] == [*range(1, steps), steps - 1]
    admits = [e for e in prof.events if e.name == "engine.admit"]
    assert sorted(i for a in admits for i in a.args["rids"]) == [0, 1, 2]
    assert [a.args["prompt_tokens"] for a in admits] == [6, 3]


def test_engine_counts_only_consumed_caches():
    """``engine.cache_donations`` counts the steps that consumed the
    cache they were given: none where the step does not donate."""
    from repro.serve import Engine, Request
    spec = ModelSpec(name="m", n_layers=2, d_model=64, n_heads=4,
                     n_kv_heads=2, d_ff=128, vocab=256)
    rt = RuntimeCfg(attention_impl="naive")
    eng = Engine(spec, rt, init_params(spec, rt, jax.random.PRNGKey(0)),
                 batch_slots=2, kv_len=16)
    eng.step_fn = jax.jit(eng.serve_step)     # the engine's, not donating
    eng.submit(Request(rid=0, prompt=np.array([1, 2]), max_new=2))
    eng.run(max_steps=8)
    c = metrics.counter
    assert c("engine.steps").value == eng.n_steps == 3
    assert c("engine.cache_donations").value == 0


def test_engine_admit_makes_no_device_transfer():
    from repro.serve import Engine, Request
    spec = ModelSpec(name="m", n_layers=2, d_model=64, n_heads=4,
                     n_kv_heads=2, d_ff=128, vocab=256)
    rt = RuntimeCfg(attention_impl="naive")
    eng = Engine(spec, rt, init_params(spec, rt, jax.random.PRNGKey(0)),
                 batch_slots=8, kv_len=128)
    rng = np.random.default_rng(0)
    lens = [97, 2, 50, 13, 97, 7, 31, 64, 5, 20]
    reqs = [Request(rid=i, prompt=rng.integers(1, 256, n).astype(np.int32))
            for i, n in enumerate(lens)]
    for r in reqs:
        eng.submit(r)
    with jax.transfer_guard_host_to_device("disallow"):
        eng._admit()
    assert eng.slots == reqs[:8] and eng.queue == reqs[8:]
    assert all(r._fed == 0 and r.t_admit >= r.t_submit for r in reqs[:8])
    assert all(r.t_admit is None for r in reqs[8:])
    c, h = metrics.counter, metrics.histogram
    assert c("engine.requests_admitted").value == 8
    assert c("engine.prompt_tokens_admitted").value == sum(lens[:8])
    assert h("engine.admit_s").count == 1
    assert h("engine.queue_wait_s").count == 8


# one wave of mixed prompt (1-30) and output (1-20) lengths, one row each
WAVE = [(1, 20), (30, 1), (7, 13), (16, 5), (3, 9), (24, 17), (12, 12)]


def _wave(spec):
    from repro.serve import Request
    rng = np.random.default_rng(5)
    return [Request(rid=i, max_new=o, prompt=rng.integers(
        1, spec.vocab, p).astype(np.int32)) for i, (p, o) in enumerate(WAVE)]


def _sequential(spec, rt, params, reqs, kv_len):
    """The wave served one step after another: ``make_serve_step``, an
    eager argmax of each step's logits read to the host, and each row's
    token fed back to it.  Returns each request's tokens and each step's
    routed counts (MoE)."""
    from repro.models import lm
    from repro.serve import make_serve_step
    routed = spec.moe is not None
    step = jax.jit(make_serve_step(spec, rt, routed=routed))
    cache = lm.init_cache(spec, rt, len(reqs), kv_len)
    outs, fed, counts = [[] for _ in reqs], [0] * len(reqs), []
    while any(len(o) < r.max_new for o, r in zip(outs, reqs)):
        live = [i for i, r in enumerate(reqs) if len(outs[i]) < r.max_new]
        tok = np.zeros((len(reqs), 1), np.int32)
        for i in live:
            if fed[i] < len(reqs[i].prompt):
                tok[i, 0] = reqs[i].prompt[fed[i]]
                fed[i] += 1
            else:
                tok[i, 0] = outs[i][-1]
        out = step(params, cache, jnp.asarray(tok))
        cache = out[1]
        nxt = np.asarray(jnp.argmax(out[0][:, 0], axis=-1))
        if routed:
            counts.append(np.asarray(out[2]))
        for i in live:
            if fed[i] >= len(reqs[i].prompt):
                outs[i].append(int(nxt[i]))
    return outs, counts


@pytest.mark.parametrize("arch", ["granite-34b", "deepseek-v2-236b"])
def test_engine_tokens_match_a_sequential_loop(arch):
    """The engine, which samples on the device and dispatches each step
    before it reads the one before, serves the tokens (and, for experts,
    routes the counts) of the plain loop that reads every step first."""
    from repro.serve import Engine
    spec = get(arch).smoke
    rt = RuntimeCfg(attention_impl="naive")
    params = init_params(spec, rt, jax.random.PRNGKey(2))
    eng = Engine(spec, rt, params, batch_slots=len(WAVE), kv_len=64)
    reqs = _wave(spec)
    for r in reqs:
        eng.submit(r)
    assert sorted(r.rid for r in eng.run(max_steps=64)) == list(
        range(len(WAVE)))
    want, counts = _sequential(spec, rt, params, _wave(spec), 64)
    assert [r.out for r in reqs] == want
    assert eng.n_steps == max(p + o for p, o in WAVE) - 1
    h, c = metrics.histogram, metrics.counter
    if spec.moe is not None:
        assert eng.n_steps == len(counts)
        assert h("moe.expert_tokens").newest() == [float(x.mean())
                                                   for x in counts]
        assert c("moe.routed_tokens").value == sum(int(x.sum())
                                                   for x in counts)


def test_engine_keeps_one_step_in_flight():
    """Driven as the benchmark's serve loop drives it (one ``run`` call per
    step, the cache reset between waves): one call per step, exactly one
    step in flight after every call that leaves work, every step but each
    wave's first dispatched ahead of its predecessor's read, none in flight
    once a wave's last request is returned, and one compiled step."""
    from repro.models import lm
    from repro.serve import Engine
    spec = get("granite-34b").smoke
    rt = RuntimeCfg(attention_impl="naive")
    eng = Engine(spec, rt, init_params(spec, rt, jax.random.PRNGKey(0)),
                 batch_slots=len(WAVE), kv_len=64)
    h, c = metrics.histogram, metrics.counter
    steps = max(p + o for p, o in WAVE) - 1
    for wave in range(2):
        reqs, done = _wave(spec), 0
        for r in reqs:
            eng.submit(r)
        for calls in range(1, steps + 3):
            done += len(eng.run(max_steps=1))
            if done == len(reqs):
                break
            assert eng._pending[0] == eng.n_steps - 1
            assert h("engine.useful_rows").count == eng.n_steps - 1
        assert eng._pending is None
        assert h("engine.useful_rows").count == eng.n_steps
        assert calls == steps and eng.n_steps == (wave + 1) * steps
        assert c("engine.steps_ahead").value == eng.n_steps - (wave + 1)
        assert all(r.done and len(r.out) == r.max_new for r in reqs)
        eng.cache = None
        eng.cache = lm.init_cache(spec, rt, len(WAVE), 64)
    assert eng.step_fn._cache_size() == 1


def test_engine_step_program_is_named_serve_step():
    """The trace's readers find the decode step's device time by the
    substring ``serve_step`` in its module's name."""
    from repro.serve import Engine
    spec = get("granite-34b").smoke
    rt = RuntimeCfg(attention_impl="naive")
    eng = Engine(spec, rt, init_params(spec, rt, jax.random.PRNGKey(0)),
                 batch_slots=2, kv_len=16)
    hlo = eng.step_fn.lower(eng.params, eng.cache, np.zeros(2, np.int32),
                            eng._tokens).compile().as_text()
    assert "serve_step" in hlo.splitlines()[0].split(",")[0]


# --------------------------------------------------------------------------
# named scopes in the model
# --------------------------------------------------------------------------

COMMON = ("embed", "lm_head", "loss", "grad", "adamw", "clip")


def _hlo_scopes(arch: str) -> set:
    from jax._src.lib import xla_client as xc

    from repro.launch.train import runtime_cfg
    from repro.train import OptCfg, init_opt_state, make_train_step
    spec = get(arch).smoke
    rt = runtime_cfg(64)
    params = jax.eval_shape(lambda k: init_params(spec, rt, k),
                            jax.random.PRNGKey(0))
    opt = jax.eval_shape(init_opt_state, params)
    batch = {k: jax.ShapeDtypeStruct((2, 64), jnp.int32)
             for k in ("tokens", "labels")}
    low = jax.jit(make_train_step(spec, rt, OptCfg())).lower(params, opt,
                                                             batch)
    opts = xc._xla.HloPrintOptions.short_parsable()
    opts.print_metadata = True
    hlo = low.compiler_ir("hlo").as_hlo_module().to_string(opts)
    found = set()
    for name in re.findall(r'op_name="([^"]*)"', hlo):
        for part in name.split("/"):
            while (m := re.fullmatch(r"[\w.]+\((.*)\)", part)):
                part = m.group(1)           # jvp(ffn) -> ffn
            found.add(part)
    return found


@pytest.mark.parametrize("arch,layers", [
    ("rwkv6-7b", ("rwkv6_time_mix", "rwkv6_channel_mix")),
    ("granite-34b", ("gqa_attention", "ffn")),
])
def test_train_step_hlo_carries_named_scopes(arch, layers):
    assert set(COMMON + layers) <= _hlo_scopes(arch)
