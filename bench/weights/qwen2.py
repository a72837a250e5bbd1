"""The benchmark's own weights of a Qwen2-family decoder, made from the
seed on the device.  A configuration names this file under ``weights``;
another family brings a file of its own.

Both the system under test and the plain reference are given these: the
reference takes nothing that the program makes.  The tree is the program's
parameter layout (layers stacked on a leading axis under
``units/b0``), which ``check_layout`` holds against the program's own
abstract shapes, so that a changed layout fails loudly at set-up.

The scales keep a random stack well conditioned, as a trained one is:
each projection has unit gain (standard deviation 1/sqrt(fan-in)), the
two that write into the residual stream are further scaled by
1/sqrt(2 x layers), norms sit near 1, and the tied table has standard
deviation 0.02.  With the program's own initializer (standard deviation
1/sqrt(layers) for every stacked weight) the stack is chaotic, and bf16
and f32 passes of the same weights part by a fifth of the largest logit.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from lib.common import seed_key


def shapes(config):
    """{path: (shape, kind)} of every weight; ``kind`` picks its scale."""
    m = config["model"]
    L, d, V = m["num_hidden_layers"], m["hidden_size"], m["vocab_size"]
    H, KV, F = (m["num_attention_heads"], m["num_key_value_heads"],
                m["intermediate_size"])
    dh = d // H
    out = {
        ("embed", "table"): ((V, d), "table"),
        ("final_norm", "scale"): ((d,), "norm"),
    }
    layer = {
        ("ln1", "scale"): ((d,), "norm"),
        ("ln2", "scale"): ((d,), "norm"),
        ("attn", "wq"): ((d, H, dh), d),
        ("attn", "wk"): ((d, KV, dh), d),
        ("attn", "wv"): ((d, KV, dh), d),
        ("attn", "bq"): ((H, dh), "bias"),
        ("attn", "bk"): ((KV, dh), "bias"),
        ("attn", "bv"): ((KV, dh), "bias"),
        ("attn", "wo"): ((H, dh, d), ("residual", H * dh)),
        ("mlp", "wi_gate"): ((d, F), d),
        ("mlp", "wi_up"): ((d, F), d),
        ("mlp", "wo"): ((F, d), ("residual", F)),
    }
    for path, (shape, kind) in layer.items():
        out[("units", "b0") + path] = ((L,) + shape, kind)
    vis = config.get("vision")
    if vis:
        df = vis["projector_in"]
        out[("projector", "ln", "scale")] = ((df,), "norm")
        out[("projector", "w1")] = ((df, d), df)
        out[("projector", "w2")] = ((d, d), d)
    return out


def _std(kind, layers):
    if kind == "table":
        return 0.02
    if kind == "bias":
        return 0.1
    if isinstance(kind, tuple):                       # ("residual", fan_in)
        return 1.0 / math.sqrt(kind[1] * 2 * layers)
    return 1.0 / math.sqrt(kind)


def build(config, key, dtype):
    """The weights tree from a PRNG key (traceable)."""
    layers = config["model"]["num_hidden_layers"]
    tree = {}
    for i, (path, (shape, kind)) in enumerate(sorted(shapes(config).items())):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        w = 1.0 + 0.1 * z if kind == "norm" else _std(kind, layers) * z
        node = tree
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = w.astype(dtype)
    return tree


def make(config, seed, dtype=jnp.bfloat16, shardings=None):
    """All weights in ``dtype``, made in one jitted call from ``seed``
    (into ``shardings`` where given)."""
    return jax.jit(lambda k: build(config, k, dtype),
                   out_shardings=shardings)(seed_key(seed))


def check_layout(weights, abstract):
    """Raise unless ``weights`` has the program's paths and shapes."""
    got = {jax.tree_util.keystr(p): a.shape
           for p, a in jax.tree_util.tree_leaves_with_path(weights)}
    want = {jax.tree_util.keystr(p): a.shape
            for p, a in jax.tree_util.tree_leaves_with_path(abstract)}
    if got != want:
        raise ValueError(
            "the program's parameter layout changed: "
            f"missing {sorted(set(want) - set(got))}, "
            f"extra {sorted(set(got) - set(want))}, differing "
            f"{sorted(k for k in got.keys() & want.keys() if got[k] != want[k])}")
