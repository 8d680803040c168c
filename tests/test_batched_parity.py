"""Batched-vs-compiled backend parity and batched-sweep semantics.

The batched backend (repro.core.batched) lowers each compiled structure
class into one jitted array kernel and replays whole batches of configs
at once.  It must reproduce the compiled backend — itself pinned exactly
against the sympy reference — within rel 1e-6 on every bundled
architecture in train and serve mode, which on CPU requires float64
(there is a regression test demonstrating float32 is NOT sufficient).

Tolerances: step/compute/comm/peak-memory components are compared at
rel 1e-6; exposed comm and bubble fraction are differences of
near-equal quantities (span - busy), so they are compared with an
absolute tolerance scaled by the step time instead of a relative one.
"""
import dataclasses

import pytest

from repro import Scenario, TPU_V5E
from repro.api import _batched_engines, _engines
from repro.configs import ARCHS, get
from repro.core.batched import REPLAYABLE_SCHEDULES, BatchedBackend
from repro.core.dse import evaluate_point_compiled

MODES = ("train", "serve")
REL = 1e-6

try:
    from benchmarks.paper_models import GPT3_5B
except ImportError:
    from repro.core import ModelSpec
    GPT3_5B = ModelSpec(name="gpt3-5b", n_layers=24, d_model=4096,
                        n_heads=32, n_kv_heads=32, d_ff=16384, vocab=51200,
                        gated_ffn=False)

GPT3_SMOKE = dataclasses.replace(GPT3_5B, name="gpt3-5b-smoke", n_layers=8,
                                 d_model=2048, n_heads=16, n_kv_heads=16,
                                 d_ff=8192, vocab=4096)


def _scenario(spec, mode):
    sc = Scenario(spec)
    if mode == "train":
        sc = sc.train(batch=8, seq=64)
    else:
        sc = sc.serve(batch=4, kv_len=128)
    return sc


def _cfgs(sc, spec):
    """One dense pp=1 config and one pipelined 1f1b config per case —
    two batch kernels, which keeps the jit-compile bill bounded while
    covering both scheduling paths of the batched evaluator."""
    ep = spec.moe is not None
    return [sc.parallel(dp=2, tp=2, sp=True, ep=ep).cfg,
            sc.parallel(dp=2, tp=2, sp=True, pp=2, microbatches=2,
                        ep=ep).cfg]


def _assert_sim_close(sim_b, sim_c, ctx):
    step = sim_c.step_time
    for attr in ("step_time", "compute_time", "comm_time"):
        a, b = getattr(sim_c, attr), getattr(sim_b, attr)
        assert abs(a - b) <= REL * max(abs(a), 1e-30), (ctx, attr, a, b)
    # span-minus-busy quantities: catastrophic cancellation makes a
    # relative bound meaningless, so bound the absolute error by step
    assert abs(sim_c.exposed_comm - sim_b.exposed_comm) <= REL * step, ctx
    assert abs(sim_c.bubble_fraction - sim_b.bubble_fraction) <= REL, ctx
    assert sim_b.schedule == sim_c.schedule, ctx


def _assert_mem_close(mem_b, mem_c, ctx):
    for f in ("weights", "grads", "opt_states", "master_params",
              "peak_activation", "recompute_extra", "peak_bytes"):
        a, b = getattr(mem_c, f), getattr(mem_b, f)
        assert abs(a - b) <= REL * max(abs(a), 1e-30), (ctx, f, a, b)
    assert mem_b.inflight_factor == mem_c.inflight_factor, ctx


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("name", ARCHS)
def test_batched_parity(name, mode):
    spec = get(name).smoke
    sc = _scenario(spec, mode)
    env = sc.env()
    engine = _engines.engine(sc.spec, sc.mode, env)
    bengine = _batched_engines.engine(sc.spec, sc.mode, env)
    cfgs = _cfgs(sc, spec)
    for recompute in ((False, True) if mode == "train" else (False,)):
        got = bengine.evaluate_many(cfgs, TPU_V5E, recompute=recompute)
        assert all(r is not None for r in got)
        for cfg, (sim_b, mem_b) in zip(cfgs, got):
            ref = evaluate_point_compiled(engine, cfg, TPU_V5E,
                                          recompute=recompute, reuse=True)
            ctx = (name, mode, cfg.describe(), recompute)
            _assert_sim_close(sim_b, ref.sim, ctx)
            _assert_mem_close(mem_b, ref.mem, ctx)


@pytest.mark.parametrize("sched", REPLAYABLE_SCHEDULES)
def test_batched_parity_schedules(sched):
    """Replayable pipeline schedules at pp=4: the planned-event replay
    scan must match the reference replay exactly (to float64)."""
    vs = 2 if sched == "interleaved" else 1
    sc = (Scenario(GPT3_SMOKE).train(batch=8, seq=128)
          .parallel(dp=2, pp=4, microbatches=8)
          .schedule(sched, vstages=vs))
    env = sc.env()
    engine = _engines.engine(sc.spec, sc.mode, env)
    bengine = _batched_engines.engine(sc.spec, sc.mode, env)
    got = bengine.evaluate_many([sc.cfg], TPU_V5E)
    assert got[0] is not None
    ref = evaluate_point_compiled(engine, sc.cfg, TPU_V5E, reuse=True)
    _assert_sim_close(got[0][0], ref.sim, sched)
    _assert_mem_close(got[0][1], ref.mem, sched)


def test_zb_h1_falls_back():
    """zb-h1 backfills weight-grad slots duration-dependently — not
    batch-replayable, so evaluate_many must decline (None) and the
    sweep must transparently take the per-config path instead."""
    sc = (Scenario(GPT3_SMOKE).train(batch=8, seq=128)
          .parallel(dp=2, pp=4, microbatches=8).schedule("zb-h1"))
    env = sc.env()
    bengine = _batched_engines.engine(sc.spec, sc.mode, env)
    assert bengine.evaluate_many([sc.cfg], TPU_V5E) == [None]
    assert not bengine.supports(sc.cfg, TPU_V5E)


def test_batched_sweep_matches_compiled():
    """Whole-sweep equivalence through the public API: same configs,
    same skip list, per-config results within the parity budget."""
    spec = get("qwen3-14b").smoke
    sc = Scenario(spec).train(batch=8, seq=64)
    kw = dict(microbatches=(1, 2), schedule=("1f1b", "gpipe"))
    ref = sc.sweep(8, **kw)
    got = sc.with_backend("batched").sweep(8, **kw)
    assert len(ref) == len(got) > 0
    assert len(ref.skipped) == len(got.skipped)
    by_label = {p.label: p for p in got}
    assert set(by_label) == {p.label for p in ref}
    for p in ref:
        q = by_label[p.label]
        _assert_sim_close(q.sim, p.sim, p.label)
        _assert_mem_close(q.mem, p.mem, p.label)
    bs = got.batch_stats
    assert bs is not None and bs["points"] >= len(got)
    assert "batched:" in got.summary()


def test_batched_backend_requires_x64():
    """The backend evaluates in float64 inside its own scoped x64 switch
    and leaves the process-wide default dtypes (what the model runtime
    sees) untouched."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    sc = _scenario(get("qwen3-14b").smoke, "train")
    backend = BatchedBackend(_engines.engine(sc.spec, sc.mode, sc.env()))
    assert backend.evaluate_many(_cfgs(sc, sc.spec)[:1], TPU_V5E)[0]
    kern = next(iter(backend._kernels.values()))
    out = kern.run_async(np.ones((1, len(kern.axes))), np.ones(1), TPU_V5E)
    assert out["step"].dtype == np.float64
    assert not jax.config.jax_enable_x64
    assert jnp.zeros(1).dtype == jnp.float32


def _sim_rel_err(backend, sc):
    sim_b, _ = backend.evaluate_many([sc.cfg], TPU_V5E, recompute=True)[0]
    ref = evaluate_point_compiled(_engines.engine(sc.spec, sc.mode, sc.env()),
                                  sc.cfg, TPU_V5E, recompute=True, reuse=True)
    return max(abs(getattr(ref.sim, a) - getattr(sim_b, a))
               / abs(getattr(ref.sim, a))
               for a in ("step_time", "compute_time", "comm_time"))


def test_float32_breaks_parity():
    """The 1e-6 budget genuinely needs float64: on a deep-pipeline
    32-layer config the float32-forced batched backend accumulates past
    the budget while the float64 default stays well inside it
    (regression guard for the x64 guard above)."""
    spec = dataclasses.replace(GPT3_SMOKE, name="gpt3-l32", n_layers=32)
    sc = Scenario(spec).train(batch=32, seq=512).parallel(
        dp=2, tp=2, sp=True, pp=4, microbatches=16)
    engine = _engines.engine(sc.spec, sc.mode, sc.env())
    assert _sim_rel_err(BatchedBackend(engine, dtype="float32"), sc) > REL
    assert _sim_rel_err(BatchedBackend(engine), sc) < REL / 100


def test_batch_bind_matches_local():
    """CostProgram.batch_bind is the vectorized _local: exact equality
    on every structure class of a small sweep."""
    import numpy as np
    from repro.core.dse import enumerate_configs
    spec = get("qwen3-14b").smoke
    sc = Scenario(spec).train(batch=8, seq=64)
    engine = _engines.engine(sc.spec, sc.mode, sc.env())
    cfgs = [c for c in enumerate_configs(8) if max(1, c.pp) == 1]
    progs = {}
    for cfg in cfgs:
        progs.setdefault(id(engine.program(cfg)), []).append(cfg)
    assert progs
    for group in progs.values():
        prog = engine.program(group[0])
        axes = tuple(sorted({a for c in group for a in c.axes}))
        ln, lb = prog.batch_bind([{a: c.axes.get(a, 1) for a in axes}
                                  for c in group], axes=axes)
        for j, cfg in enumerate(group):
            rn, rb = prog._local(cfg)
            assert np.array_equal(ln[j], rn), cfg.describe()
            assert np.array_equal(lb[j], rb), cfg.describe()


def test_batched_single_point_api():
    """A batched-backend Scenario still traces/simulates per point via
    the shared compiled engine (batched only changes sweep)."""
    sc = _scenario(get("qwen3-14b").smoke, "train") \
        .parallel(dp=2, tp=2, sp=True).with_backend("batched")
    ref = sc.with_backend("compiled").trace().simulate(TPU_V5E)
    got = sc.trace().simulate(TPU_V5E)
    assert got.step_time == ref.step_time
