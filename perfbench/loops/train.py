"""Closed-loop training: the runtime trainer's step, one after another.

Set-up composes what ``repro.launch.train`` composes (``runtime_cfg``,
``make_local_mesh``, ``arch_rules``, sharded params and optimizer state,
the donating ``make_train_step``) with the benchmark's seeded weights,
then drives the first ``check_steps`` steps through the window's own
call and feed.  It reads, as the state leaves them, each step's loss,
the first gradient as the optimizer got it (Adam's first moment after
one step over 1 - b1) and, after the last of them, the parameters'
change.  The window runs further steps on the same objects, each ending
in the host's read of its loss.  After the window the plain reference
(``refs/<reference>.py`` and ``refs/adamw.py``) repeats those first
steps from the same seed and the numbers are compared.

Traffic keys: ``seq``, ``batch``, ``check_steps``, ``optimizer`` (the
AdamW settings the configuration is trained with), ``trace_seconds``
(how much of a traced window the profiler records; all of it if absent).
"""
from __future__ import annotations

import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np

import gen
import weights

TOKENS = "train_tokens_per_s"


def build(ctx, spec):
    """The program's objects, as ``repro.launch.train`` builds them."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_local_mesh
    from repro.launch.train import runtime_cfg
    from repro.models import init_params
    from repro.models.common import AxisRules
    from repro.parallel.sharding import (arch_rules, data_axes_of,
                                         param_shardings)
    from repro.train import OptCfg, init_opt_state, make_train_step
    from repro.train.optimizer import opt_state_shardings

    tr = ctx.traffic
    rt = runtime_cfg(tr["seq"])
    mesh = make_local_mesh()
    rules_d = arch_rules(spec, mesh, sp=rt.sp)
    rules = AxisRules(rules_d)
    rules.mesh = mesh
    da = data_axes_of(mesh)
    b_shard = NamedSharding(mesh, P(da))
    with jax.set_mesh(mesh):
        abstract = jax.eval_shape(lambda k: init_params(spec, rt, k),
                                  jax.random.PRNGKey(0))
        p_shard = param_shardings(abstract, rules_d, mesh)
        params, lay = weights.make_tree(abstract, ctx.seed,
                                        ctx.config.get("init_std", {}),
                                        p_shard)
        o_shard = opt_state_shardings(params, rules_d, mesh,
                                      zero1=rt.zero1, data_axes=da)
        opt = jax.jit(init_opt_state, out_shardings=o_shard)(params)
        step_fn = jax.jit(
            make_train_step(spec, rt, OptCfg(**tr["optimizer"]), rules),
            in_shardings=(p_shard, o_shard, b_shard),
            out_shardings=(p_shard, o_shard, NamedSharding(mesh, P())),
            donate_argnums=(0, 1))
    return {"mesh": mesh, "params": params, "opt": opt, "step_fn": step_fn,
            "b_shard": b_shard, "lay": lay}


def leaf_norms(tree) -> list:
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree_util.tree_leaves(tree)]


def setup(ctx) -> dict:
    from harness import runtime_spec
    tr = ctx.traffic
    spec = runtime_spec(ctx.config)
    st = build(ctx, spec)
    st.update(spec=spec, step=0, losses=[])
    m_norms = jax.jit(lambda o: leaf_norms(o["m"]))
    change = jax.jit(lambda a, b: leaf_norms(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b)))

    with jax.set_mesh(st["mesh"]):
        p0 = jax.tree.map(jnp.copy, st["params"])
        for i in range(tr["check_steps"]):
            st["losses"].append(unit(ctx, st))
            if i == 0:
                b1 = tr["optimizer"]["b1"]
                st["grad_norms"] = [float(x) / (1 - b1)
                                    for x in m_norms(st["opt"])]
        st["change_norms"] = [float(x) for x in change(st["params"], p0)]
        del p0
    return st


def unit(ctx, st) -> float:
    """One step through the window's own call and feed; ends on the
    host's read of its loss."""
    tr = ctx.traffic
    batch = gen.train_batch(st["step"], tr["batch"], tr["seq"],
                            st["spec"].vocab, ctx.seed)
    batch = {k: jax.device_put(v, st["b_shard"]) for k, v in batch.items()}
    st["params"], st["opt"], m = st["step_fn"](st["params"], st["opt"],
                                               batch)
    st["step"] += 1
    return float(m["loss"])


def window(ctx, st, seconds: float, trace: bool) -> dict:
    from harness import Window
    tr = ctx.traffic
    steps, bad, times = 0, 0, []
    with jax.set_mesh(st["mesh"]), \
            Window(seconds, trace, tr.get("trace_seconds")) as w:
        while w.open():
            t = time.perf_counter()
            with w.phase("train.step"):
                loss = unit(ctx, st)
            times.append(time.perf_counter() - t)
            steps += 1
            bad += not np.isfinite(loss)
    tokens = steps * tr["batch"] * tr["seq"]
    med = float(np.median(times))
    slow = [x for x in times if x > 1.5 * med]
    print(f"perfbench: {steps} steps, median {med * 1e3:.2f} ms, max "
          f"{max(times) * 1e3:.2f} ms; {len(slow)} over 1.5x the median "
          f"took {sum(slow) - med * len(slow):.3f} s more", flush=True)
    return {"attempted": steps, "failed": bad, "steps": steps,
            "tokens": tokens, "seconds": w.elapsed, "trace": w.reduced,
            "metrics": {TOKENS: tokens / w.elapsed},
            "step_ms_median": med * 1e3,
            "losses": st["losses"], "grad_norms": st["grad_norms"],
            "change_norms": st["change_norms"], "lay": st["lay"]}


def release(ctx, st) -> None:
    st.clear()


def reference(ctx, lay: list, mode: str) -> dict:
    """The reference's readings for the first ``check_steps`` steps."""
    import adamw
    tr = ctx.traffic
    o = tr["optimizer"]
    ref = importlib.import_module(ctx.config["reference"])
    overrides = ctx.config.get("init_std", {})
    names = [p for p, _, _ in lay]
    vocab = ctx.config["spec"]["vocab"]

    params = dict(zip(names, weights.make_flat(lay, ctx.seed, overrides)))
    p0 = {k: jnp.copy(v) for k, v in params.items()}
    m = {k: jnp.zeros(v.shape, jnp.float32) for k, v in params.items()}
    v_ = {k: jnp.zeros(v.shape, jnp.float32) for k, v in params.items()}
    losses, grad_norms = [], None

    def one(params, m, v_, tokens, labels, scalars):
        loss, g = jax.value_and_grad(ref.loss)(params, tokens, labels, mode)
        scale = adamw.clip_scale(g, o)
        gn = {k: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32) * scale)))
              for k, x in g.items()}
        P, M, V = adamw.update(params, g, m, v_, scalars, o)
        return loss, gn, P, M, V

    one = jax.jit(one, donate_argnums=(0, 1, 2))
    for step in range(tr["check_steps"]):
        b = gen.train_batch(step, tr["batch"], tr["seq"], vocab, ctx.seed)
        scalars = tuple(jnp.float32(x) for x in adamw.step_scalars(o, step))
        loss, gn, params, m, v_ = one(params, m, v_, jnp.asarray(b["tokens"]),
                                      jnp.asarray(b["labels"]), scalars)
        losses.append(float(loss))
        if step == 0:
            grad_norms = [float(gn[k]) for k in names]
    del m, v_
    change = [float(jnp.sqrt(jnp.sum(jnp.square(
        params[k].astype(jnp.float32) - p0[k].astype(jnp.float32)))))
        for k in names]
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}


def gaps(prog: dict, ref: dict) -> dict:
    """The numbers compared: the relative loss gap, as the mean over the
    steps; the gap between the program's and the reference's norms of the
    first gradient, by the median leaf; and of the change, by the worst
    leaf.  A leaf's gap is over the larger of its reference norm and the
    median leaf's.  Leaves whose reference gradient is under a thousandth
    of the median leaf's move by round-off alone and are left out of all.

    The steadier forms are chosen by what the readings showed: the worst
    leaf's gradient gap is rounding noise of the WKV path (``u``, ``w_r``,
    ``w_k``, whose gradients cancel under the per-head norm), which the
    reference in bfloat16 shares; and the mean over the steps of the loss
    gap separates the float8 control, where the worst step does not."""
    per_step = [abs(a - b) / abs(b) for a, b in zip(prog["losses"],
                                                    ref["losses"])]
    g_ref = np.asarray(ref["grad_norms"])
    keep = g_ref >= 1e-3 * np.median(g_ref)

    def rel(key):
        a, b = np.asarray(prog[key]), np.asarray(ref[key])
        r = np.abs(a - b) / np.maximum(b, np.median(b[keep]))
        return np.where(keep, r, 0.0)

    g, c = rel("grad_norms"), rel("change_norms")
    return {"loss_rel_mean": float(np.mean(per_step)),
            "loss_rel_worst": float(max(per_step)),
            "grad_norm_rel_median": float(np.median(g[keep])),
            "change_norm_rel": float(c.max()),
            "grad_norm_rel_worst": float(g.max()),
            "leaves_compared": int(keep.sum()),
            "worst_leaves": (int(g.argmax()), int(c.argmax()))}


COMPARED = ("loss_rel_mean", "grad_norm_rel_median", "change_norm_rel")


def check(ctx, win: dict, mode: str = "f32") -> dict:
    t0 = time.perf_counter()
    ref = reference(ctx, win["lay"], mode)
    g = gaps(win, ref)
    lim = ctx.limits
    out = {k: {"value": g[k], "limit": lim[k]} for k in COMPARED}
    print(f"perfbench: reference {time.perf_counter() - t0:.1f} s, "
          f"{g['leaves_compared']} of {len(win['lay'])} leaves compared; "
          f"losses program {win['losses']} reference {ref['losses']}",
          flush=True)
    for what, i in zip(("grad_norms", "change_norms"), g["worst_leaves"]):
        print(f"perfbench: worst {what} leaf {win['lay'][i][0]}: program "
              f"{win[what][i]} reference {ref[what][i]}", flush=True)
    return out
