"""Plain AdamW with global-norm clipping and warm-up then cosine decay, as
the configuration's optimizer settings state, in float32.

The learning rate at step t (counted from 1) is lr * min(t / warmup, 1) *
(f + (1 - f) * (1 + cos(pi * p)) / 2), p = clip((t - warmup) /
(decay_steps - warmup), 0, 1), f = min_lr_frac.  Gradients are scaled by
min(1, clip / (|g| + 1e-9)) before the moments; the update is
m^ / (sqrt(v^) + eps) + weight_decay * p.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def lr_at(o, t):
    warm = min(t / max(o["warmup"], 1), 1.0)
    p = min(max((t - o["warmup"]) / max(o["decay_steps"] - o["warmup"], 1),
                0.0), 1.0)
    f = o["min_lr_frac"]
    return o["lr"] * warm * (f + (1 - f) * 0.5 * (1 + math.cos(math.pi * p)))


def clip(grads, max_norm):
    n = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    s = jnp.minimum(1.0, max_norm / (n + 1e-9))
    return jax.tree.map(lambda g: g * s, grads)


def step(o, t, params, m, v, grads):
    """One update at step ``t`` (from 1) of float32 trees; returns
    (params, m, v) and the clipped gradient."""
    g = clip(grads, o["grad_clip"])
    b1, b2 = o["b1"], o["b2"]
    m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
    v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
    lr = lr_at(o, t)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree.map(
        lambda p, a, b: p - lr * ((a / c1) / (jnp.sqrt(b / c2) + o["eps"])
                                  + o["weight_decay"] * p), params, m, v)
    return params, m, v, g
