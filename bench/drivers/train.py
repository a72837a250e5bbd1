"""Training: the step of ``launch/steps.build_cell``, run as
``launch/train.py`` runs it (jitted with the cell's shardings and donation,
parameters and optimizer state made under ``jit`` into their shardings).

Set-up builds that one step with its state, from the seed, and drives it
through its first three steps on the window's own feed (rows that all
differ); those steps compile it.  Before step 2 overwrites the optimizer
state, the norm of each leaf of the first gradient as the optimizer got it
(its first moment over 1 - b1) is read, and after step 3 the norm of each
leaf's change from the initial parameters (made again from the seed).  The
window then runs steps 4, 5, ... for ``--seconds``, each timed to
``block_until_ready``; ``train_tokens_per_s`` is every position of every
step completed over the time from the window's start to the end of its last
step.

``correct``: the plain reference (the file the configuration names under
``reference``, in float32, with ``reference/adamw.py``) follows the same three steps from the same weights
and rows, after the window, when the program's state is freed.  Compared:
each step's loss, and, leaf by leaf, the norms of the first gradient and of
the change after three steps, each as the gap between the program's norm
and the reference's over the larger of the reference's norm of that leaf
and of the median leaf.  Leaves whose reference gradient is under a
thousandth of the median leaf's move under Adam by round-off alone and are
left out of the change.
"""
from __future__ import annotations

import time

import numpy as np

N_CHECKED = 3


def _keyed(tree):
    import jax
    return {jax.tree_util.keystr(p): a
            for p, a in jax.tree_util.tree_leaves_with_path(tree)}


def _norms(tree, scale=1.0):
    import jax.numpy as jnp
    return {k: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))) * scale
            for k, a in _keyed(tree).items()}


def _opt_settings(cell):
    o = {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
         "weight_decay": 0.1, "grad_clip": 1.0, "warmup": 100,
         "decay_steps": 10000, "min_lr_frac": 0.1}
    o.update(cell.get("optimizer", {}))
    return o


def setup(ctx, fault=None):
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType, Mesh
    from lib import common
    from repro.configs.base import RunPolicy
    from repro.configs.base import ShapeSpec
    from repro.launch.sharding import use_rules
    from repro.launch.steps import build_cell
    from repro.models import api
    from repro.train.optimizer import OptConfig
    from repro.train.train_step import make_init_opt

    files, seed = ctx.files, ctx.seed
    cell, config, mix = files["cell"], files["config"], files["mix"]
    tr = cell["training"]
    o = _opt_settings(cell)
    W = common.config_module(config, "weights")
    cfg = common.config_module(config, "program").program_config(config)
    policy = RunPolicy(**cell["policy"])
    opt = OptConfig(name="adamw", **o)
    model_axis = tr.get("model_axis", 1)
    mesh = Mesh(np.asarray(ctx.devices).reshape(-1, model_axis),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    shape = ShapeSpec("train", "train", mix["seq"], tr["batch"])
    with ctx.spans("setup"):
        pc = build_cell(cfg, shape, policy, mesh, opt)
        pshard, oshard, bshard = pc.in_shardings
        with mesh, use_rules(mesh, pc.rules):
            params = W.make(config, seed, jnp.float32, pshard)
            W.check_layout(params, api.abstract_params(cfg))
            opt_state = jax.jit(make_init_opt(cfg, policy, opt, mesh),
                                out_shardings=oshard)(params)
            fn = pc.fn if fault is None else fault(pc.fn)
            step_fn = jax.jit(fn, in_shardings=pc.in_shardings,
                              out_shardings=pc.out_shardings,
                              donate_argnums=pc.donate_argnums)
            feed = ctx.generator(mix["kind"]).feed(mix, config, tr["batch"],
                                                   seed, bshard)
            grad_norms = jax.jit(lambda m: _norms(m, 1.0 / (1.0 - o["b1"])))
            change_norms = jax.jit(lambda p, k: _norms(jax.tree.map(
                lambda a, b: a - b, p, W.build(config, k, jnp.float32))))
            losses = []
            for i in range(N_CHECKED):
                params, opt_state, m = step_fn(params, opt_state, feed(i))
                losses.append(m["loss"])
                if i == 0:
                    g1 = grad_norms(opt_state["mom"]["m"])
            dp = change_norms(params, common.seed_key(seed))
            jax.block_until_ready((params, opt_state, dp))
    return {"mesh": mesh, "rules": pc.rules, "params": params,
            "opt_state": opt_state, "step_fn": step_fn, "feed": feed,
            "opt": o, "batch": tr["batch"], "seq": mix["seq"],
            "program": {"loss": [float(x) for x in losses],
                        "grad": {k: float(v) for k, v in g1.items()},
                        "change": {k: float(v) for k, v in dp.items()}}}


def window(ctx, st):
    import jax
    from repro.launch.sharding import use_rules
    params, opt_state = st["params"], st["opt_state"]
    step_fn, feed = st["step_fn"], st["feed"]
    spans = ctx.spans
    i, steps = N_CHECKED, 0
    with st["mesh"], use_rules(st["mesh"], st["rules"]):
        ctx.window_starts()
        t0 = time.perf_counter()
        t_end = t0 + ctx.seconds
        t = t0
        while t < t_end:
            ctx.tracer(t - t0)
            with spans("data"):
                batch = feed(i)
            with spans("step"):
                params, opt_state, m = step_fn(params, opt_state, batch)
                jax.block_until_ready((params, opt_state, m))
            t = time.perf_counter()
            i += 1
            steps += 1
        ctx.tracer(None)
        ctx.window_ends()
    ctx.memory_peak()
    st["params"] = st["opt_state"] = None
    del params, opt_state
    return {"steps": steps, "seconds": t - t0,
            "tokens": steps * st["batch"] * st["seq"]}


def reference(ctx, st, quant=None):
    """The reference's three steps from the seed's weights, on the rows of
    the program's first three steps: losses, first-gradient and change
    norms by leaf."""
    import jax
    import jax.numpy as jnp
    from lib import common
    from reference import adamw

    config, o = ctx.files["config"], st["opt"]
    W = common.config_module(config, "weights")
    ref_model = common.config_module(config, "reference")
    params = W.make(config, ctx.seed, jnp.float32)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    row_grad = jax.jit(jax.value_and_grad(
        lambda w, row: ref_model.loss(w, row, config, quant)))
    update = jax.jit(adamw.step, static_argnums=(0, 1),
                     donate_argnums=(2, 3, 4, 5))
    acc = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=(0, 1))
    scale = jax.jit(lambda a, s: jax.tree.map(lambda x: x * s, a),
                    donate_argnums=0)
    norms = jax.jit(_norms)
    losses = []
    with jax.default_matmul_precision("highest"):
        for i in range(N_CHECKED):
            batch = st["feed"](i)
            rows = st["batch"]
            total, gsum = 0.0, None
            for r in range(rows):          # rows carry equal label counts
                row = {k: a[r:r + 1] for k, a in batch.items()}
                loss, g = row_grad(params, row)
                total += float(loss)
                gsum = g if gsum is None else acc(gsum, g)
            losses.append(total / rows)
            params, m, v, gc = update(_hashable(o), i + 1, params, m, v,
                                      scale(gsum, 1.0 / rows))
            if i == 0:
                g1 = {k: float(x) for k, x in norms(gc).items()}
            del gc
    del m, v
    change = jax.jit(lambda p, k: _norms(jax.tree.map(
        lambda a, b: a - b, p, W.build(config, k, jnp.float32))))(
            params, common.seed_key(ctx.seed))
    return {"loss": losses, "grad": g1,
            "change": {k: float(x) for k, x in change.items()}}


class _hashable(dict):
    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def gaps(got, ref):
    """The numbers compared: the worst step's relative loss gap, and the
    worst leaf's norm gaps for the first gradient and the change."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(got["loss"], ref["loss"]))
    med_g = float(np.median(list(ref["grad"].values())))
    grad = max(abs(got["grad"][k] - r) / max(r, med_g)
               for k, r in ref["grad"].items())
    moved = [k for k, r in ref["grad"].items() if r >= 1e-3 * med_g]
    med_c = float(np.median([ref["change"][k] for k in moved]))
    change = max(abs(got["change"][k] - ref["change"][k])
                 / max(ref["change"][k], med_c) for k in moved)
    return {"loss_gap": loss, "grad_norm_gap": grad, "change_norm_gap": change,
            "unmoved_leaves": sorted(set(ref["grad"]) - set(moved))}


def check(ctx, st, quant=None):
    chk = ctx.files["cell"]["correct"]
    ref = reference(ctx, st)
    got = st["program"] if quant is None else reference(ctx, st, quant)
    g = gaps(got, ref)
    out = {k: (g[k], chk.get(k)) for k in
           ("loss_gap", "grad_norm_gap", "change_norm_gap")}
    return out, {"program": got, "reference": ref,
                 "unmoved": g["unmoved_leaves"]}


def run(ctx):
    st = setup(ctx)
    w = window(ctx, st)
    checks, detail = check(ctx, st)
    return {
        "attempted": w["steps"],
        "failed": 0,
        "checks": checks,
        "metrics": {"train_tokens_per_s": w["tokens"] / w["seconds"]},
        "notes": [f"steps {w['steps']} in {w['seconds']!r} s; losses "
                  f"program {detail['program']['loss']} reference "
                  f"{detail['reference']['loss']}; leaves left out of the "
                  f"change (reference gradient under 1e-3 of the median): "
                  f"{detail['unmoved']}"],
    }


def calibrate(ctx, rate=None, control=None, fault=None):
    st = setup(ctx, fault=FAULTS[fault] if fault else None)
    w = window(ctx, st)
    ref = reference(ctx, st)
    out = {"program": {k: v for k, v in gaps(st["program"], ref).items()
                       if k != "unmoved_leaves"},
           "tokens_per_s": w["tokens"] / w["seconds"], "steps": w["steps"],
           "losses": st["program"]["loss"], "ref_losses": ref["loss"]}
    if control:
        out["control"] = {k: v for k, v in gaps(
            reference(ctx, st, control), ref).items()
            if k != "unmoved_leaves"}
    return out


def _half_batch(fn):
    """Fault: the step sees the first half of the batch only, its mean
    taken over that half."""
    def broken(params, opt_state, batch):
        half = {k: v[:v.shape[0] // 2] for k, v in batch.items()}
        return fn(params, opt_state, half)
    return broken


def _state_unchanged(fn):
    """Fault: the step returns the state it was given."""
    def broken(params, opt_state, batch):
        _, _, m = fn(params, opt_state, batch)
        return params, opt_state, m
    return broken


FAULTS = {"half_batch": _half_batch, "state_unchanged": _state_unchanged}
