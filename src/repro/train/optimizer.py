"""Native pytree optimizers: AdamW, Adafactor (factored 2nd moment), SGD-m.

Optimizer state carries the same logical axes as its parameter (plus ZeRO-1
"data"-axis sharding applied at sharding-build time, see
``launch/sharding.zero1_spec``).  LR schedule: linear warmup + cosine decay.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class OptConfig:
    name: str = "adamw"               # adamw | adafactor | sgdm
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup: int = 100
    decay_steps: int = 10_000
    min_lr_frac: float = 0.1


def schedule(opt: OptConfig, step):
    step = step.astype(jnp.float32)
    warm = jnp.minimum(step / max(opt.warmup, 1), 1.0)
    prog = jnp.clip((step - opt.warmup) / max(opt.decay_steps - opt.warmup, 1), 0, 1)
    cos = opt.min_lr_frac + (1 - opt.min_lr_frac) * 0.5 * (1 + jnp.cos(np.pi * prog))
    return opt.lr * warm * cos


def _factored(shape):
    return len(shape) >= 2


def init_opt_state(opt: OptConfig, params):
    f32 = lambda a: jnp.zeros(a.shape, jnp.float32)
    if opt.name == "adamw":
        mom = {"m": jax.tree.map(f32, params), "v": jax.tree.map(f32, params)}
    elif opt.name == "sgdm":
        mom = {"m": jax.tree.map(f32, params)}
    elif opt.name == "adafactor":
        def vr(a):
            return jnp.zeros(a.shape[:-1], jnp.float32) if _factored(a.shape) \
                else jnp.zeros(a.shape, jnp.float32)
        def vc(a):
            return jnp.zeros(a.shape[:-2] + a.shape[-1:], jnp.float32) \
                if _factored(a.shape) else jnp.zeros((), jnp.float32)
        mom = {"vr": jax.tree.map(vr, params), "vc": jax.tree.map(vc, params)}
    else:
        raise ValueError(opt.name)
    return {"mom": mom, "step": jnp.zeros((), jnp.int32)}


def opt_state_axes(opt: OptConfig, axes_tree):
    """Logical axes for the optimizer state, parallel to init_opt_state."""
    is_ax = lambda a: isinstance(a, tuple)
    if opt.name in ("adamw", "sgdm"):
        mom_axes = {k: jax.tree.map(lambda a: a, axes_tree, is_leaf=is_ax)
                    for k in (("m", "v") if opt.name == "adamw" else ("m",))}
    else:
        mom_axes = {
            "vr": jax.tree.map(lambda a: a[:-1] if len(a) >= 2 else a,
                               axes_tree, is_leaf=is_ax),
            "vc": jax.tree.map(lambda a: a[:-2] + a[-1:] if len(a) >= 2 else (),
                               axes_tree, is_leaf=is_ax),
        }
    return {"mom": mom_axes, "step": ()}


def global_norm(tree):
    sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
             for g in jax.tree.leaves(tree))
    return jnp.sqrt(sq)


def clip_by_global_norm(grads, max_norm):
    n = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / (n + 1e-9))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale), grads), n


@jax.named_scope("optimizer")
def opt_update(opt: OptConfig, grads, state, params):
    """Returns (new_params, new_state, stats)."""
    grads, gnorm = clip_by_global_norm(grads, opt.grad_clip)
    step = state["step"] + 1
    lr = schedule(opt, step)
    mom = state["mom"]

    if opt.name == "adamw":
        b1, b2 = opt.b1, opt.b2
        m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, mom["m"], grads)
        v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * jnp.square(g),
                         mom["v"], grads)
        t = step.astype(jnp.float32)
        bc1 = 1 - b1 ** t
        bc2 = 1 - b2 ** t
        def upd(p, m_, v_):
            u = (m_ / bc1) / (jnp.sqrt(v_ / bc2) + opt.eps)
            u = u + opt.weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * u).astype(p.dtype)
        new_params = jax.tree.map(upd, params, m, v)
        new_mom = {"m": m, "v": v}
    elif opt.name == "sgdm":
        m = jax.tree.map(lambda m_, g: opt.b1 * m_ + g, mom["m"], grads)
        new_params = jax.tree.map(
            lambda p, m_: (p.astype(jnp.float32) - lr * m_).astype(p.dtype),
            params, m)
        new_mom = {"m": m}
    elif opt.name == "adafactor":
        eps = 1e-30
        def upd(p, g, vr, vc):
            g2 = jnp.square(g) + eps
            if _factored(p.shape):
                nvr = opt.b2 * vr + (1 - opt.b2) * g2.mean(axis=-1)
                nvc = opt.b2 * vc + (1 - opt.b2) * g2.mean(axis=-2)
                denom = (nvr / jnp.maximum(nvr.mean(axis=-1, keepdims=True), eps)
                         )[..., None] * nvc[..., None, :]
                u = g * jax.lax.rsqrt(denom + eps)
            else:
                nvr = opt.b2 * vr + (1 - opt.b2) * g2
                nvc = vc
                u = g * jax.lax.rsqrt(nvr + eps)
            # update clipping (Adafactor d=1.0)
            rms_u = jnp.sqrt(jnp.mean(jnp.square(u)) + eps)
            u = u / jnp.maximum(1.0, rms_u)
            u = u + opt.weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * u).astype(p.dtype), nvr, nvc
        flat_p, tdef = jax.tree.flatten(params)
        flat_g = jax.tree.leaves(grads)
        flat_vr = jax.tree.leaves(mom["vr"])
        flat_vc = jax.tree.leaves(mom["vc"])
        out = [upd(p, g, r, c) for p, g, r, c in
               zip(flat_p, flat_g, flat_vr, flat_vc)]
        new_params = jax.tree.unflatten(tdef, [o[0] for o in out])
        new_mom = {"vr": jax.tree.unflatten(tdef, [o[1] for o in out]),
                   "vc": jax.tree.unflatten(tdef, [o[2] for o in out])}
    else:
        raise ValueError(opt.name)

    return new_params, {"mom": new_mom, "step": step}, \
        {"grad_norm": gnorm, "lr": lr}
