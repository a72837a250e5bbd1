"""Shared layers: norms, activations, embeddings, RoPE, MLPs (GLU + plain)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .module import ParamSpec

# ---------------------------------------------------------------- activations

def act_fn(name: str):
    return {
        "silu": jax.nn.silu,
        "gelu": jax.nn.gelu,
        "relu": jax.nn.relu,
        "relu2": lambda x: jnp.square(jax.nn.relu(x)),
        "tanh": jnp.tanh,
    }[name]


# ---------------------------------------------------------------------- norms

def norm_specs(d: int, kind: str):
    if kind == "rmsnorm":
        return {"scale": ParamSpec((d,), ("embed",), "ones")}
    if kind == "layernorm":
        return {"scale": ParamSpec((d,), ("embed",), "ones"),
                "bias": ParamSpec((d,), ("embed",), "zeros")}
    raise ValueError(kind)


def apply_norm(p, x, kind: str, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ----------------------------------------------------------------------- RoPE

def rope_freqs(d_head: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, d_head, 2, dtype=np.float32) / d_head))


def apply_rope(x, positions, theta: float):
    """x: (..., seq, d_head) paired-halves rotary.  positions: (..., seq)."""
    d = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(d, theta))            # (d/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, d/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------- embeddings

def embed_lookup(p, tokens):
    return jnp.take(p["table"], tokens, axis=0)


# ----------------------------------------------------------------------- MLPs

def glu_mlp_specs(d: int, f: int):
    return {
        "wi_gate": ParamSpec((d, f), ("embed", "mlp")),
        "wi_up": ParamSpec((d, f), ("embed", "mlp")),
        "wo": ParamSpec((f, d), ("mlp", "embed")),
    }


def apply_glu_mlp(p, x, act: str):
    g = jnp.einsum("...d,df->...f", x, p["wi_gate"])
    u = jnp.einsum("...d,df->...f", x, p["wi_up"])
    h = act_fn(act)(g) * u
    return jnp.einsum("...f,fd->...d", h, p["wo"])


def plain_mlp_specs(d: int, f: int):
    return {
        "wi": ParamSpec((d, f), ("embed", "mlp")),
        "bi": ParamSpec((f,), ("mlp",), "zeros"),
        "wo": ParamSpec((f, d), ("mlp", "embed")),
        "bo": ParamSpec((d,), ("embed",), "zeros"),
    }


def apply_plain_mlp(p, x, act: str):
    h = act_fn(act)(jnp.einsum("...d,df->...f", x, p["wi"]) + p["bi"])
    return jnp.einsum("...f,fd->...d", h, p["wo"]) + p["bo"]
