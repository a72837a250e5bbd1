"""RecurrentGemma / Griffin recurrent block (RG-LRU + causal conv1d branch).

h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (i_t * x_t)
a_t = exp(-c * softplus(Lambda) * r_t),   r_t, i_t block-diagonal sigmoids.

Full-sequence path uses ``jax.lax.associative_scan`` (the TPU Pallas kernel
``kernels/rglru_scan.py`` implements the same recurrence blockwise); decode
updates the carried state in O(1).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .module import ParamSpec
from ..launch.sharding import maybe_constrain

C_RGLRU = 8.0
CONV_K = 4


def rglru_specs(d: int, width: int, n_blocks: int):
    wb = width // n_blocks
    return {
        "wx": ParamSpec((d, width), ("embed", "rec_width")),
        "wy": ParamSpec((d, width), ("embed", "rec_width")),
        "conv_w": ParamSpec((CONV_K, width), (None, "rec_width"), "normal", 0.1),
        "conv_b": ParamSpec((width,), ("rec_width",), "zeros"),
        "gate_a": ParamSpec((n_blocks, wb, wb), ("heads", None, None)),
        "gate_a_b": ParamSpec((n_blocks, wb), ("heads", None), "zeros"),
        "gate_i": ParamSpec((n_blocks, wb, wb), ("heads", None, None)),
        "gate_i_b": ParamSpec((n_blocks, wb), ("heads", None), "zeros"),
        "lam": ParamSpec((width,), ("rec_width",), "uniform_scale", 1.0),
        "wo": ParamSpec((width, d), ("rec_width", "embed")),
    }


def _gates(p, xb, n_blocks):
    """xb: (...,W) -> (r, i) each (...,W); block-diagonal sigmoid gates."""
    shp = xb.shape
    wb = shp[-1] // n_blocks
    xg = xb.reshape(shp[:-1] + (n_blocks, wb)).astype(jnp.float32)
    r = jax.nn.sigmoid(jnp.einsum("...nw,nwv->...nv", xg, p["gate_a"].astype(jnp.float32))
                       + p["gate_a_b"].astype(jnp.float32))
    i = jax.nn.sigmoid(jnp.einsum("...nw,nwv->...nv", xg, p["gate_i"].astype(jnp.float32))
                       + p["gate_i_b"].astype(jnp.float32))
    return r.reshape(shp), i.reshape(shp)


def _log_a(p, r):
    return -C_RGLRU * jax.nn.softplus(p["lam"].astype(jnp.float32)) * r


def _conv_full(p, xb):
    """Causal depthwise conv width CONV_K over seq axis 1 (no conv HLO op)."""
    out = p["conv_b"].astype(xb.dtype) * jnp.ones_like(xb)
    for j in range(CONV_K):
        shifted = jnp.pad(xb, ((0, 0), (j, 0), (0, 0)))[:, :xb.shape[1]]
        out = out + shifted * p["conv_w"][CONV_K - 1 - j].astype(xb.dtype)
    return out


def apply_rglru(p, x, *, n_blocks: int, use_pallas: bool = False,
                return_state: bool = False):
    """Full-sequence recurrent block. x: (B,S,D) -> (out (B,S,D), state):
    with ``return_state`` the decode state after the last position (its
    ``h`` and the conv's last ``CONV_K - 1`` inputs), else None.  The Pallas
    scan runs under ``use_pallas`` when no state is asked for."""
    xb = jnp.einsum("bsd,dw->bsw", x, p["wx"])
    xb = maybe_constrain(xb, ("batch", None, "rec_width"))
    xc = _conv_full(p, xb)
    r, i = _gates(p, xc, n_blocks)
    log_a = _log_a(p, r)                                   # (B,S,W) f32
    a = jnp.exp(log_a)
    gated = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) \
        * (i * xc.astype(jnp.float32))

    if use_pallas and not return_state:
        from ..kernels import ops
        h = ops.rglru(a, gated, use_pallas=True)
    else:
        def combine(c1, c2):
            a1, b1 = c1
            a2, b2 = c2
            return a1 * a2, a2 * b1 + b2

        _, h = jax.lax.associative_scan(combine, (a, gated), axis=1)
    y = jax.nn.gelu(jnp.einsum("bsd,dw->bsw", x, p["wy"]))
    out = jnp.einsum("bsw,wd->bsd", h.astype(x.dtype) * y, p["wo"])
    if not return_state:
        return out, None
    hist = jnp.pad(xb, ((0, 0), (CONV_K - 1, 0), (0, 0)))[:, -(CONV_K - 1):]
    return out, {"h": h[:, -1], "conv": hist}


def rglru_state_shapes(batch: int, width: int, dtype):
    return {"h": jax.ShapeDtypeStruct((batch, width), jnp.float32),
            "conv": jax.ShapeDtypeStruct((batch, CONV_K - 1, width), dtype)}


RGLRU_STATE_AXES = {"h": ("batch", "rec_width"),
                    "conv": ("batch", None, "rec_width")}


def decode_rglru(p, state, x, *, n_blocks: int):
    """One-token decode. x: (B,1,D) -> (out (B,1,D), new_state)."""
    xb = jnp.einsum("bsd,dw->bsw", x, p["wx"])[:, 0]        # (B,W)
    hist = jnp.concatenate([state["conv"], xb[:, None]], axis=1)  # (B,K,W)
    conv = p["conv_b"].astype(xb.dtype) + jnp.einsum(
        "bkw,kw->bw", hist, p["conv_w"].astype(xb.dtype))
    r, i = _gates(p, conv, n_blocks)
    log_a = _log_a(p, r)
    a = jnp.exp(log_a)
    gated = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) \
        * (i * conv.astype(jnp.float32))
    h = a * state["h"] + gated
    y = jax.nn.gelu(jnp.einsum("bsd,dw->bsw", x, p["wy"]))[:, 0]
    out = (h.astype(x.dtype) * y) @ p["wo"]
    new_state = {"h": h, "conv": hist[:, 1:]}
    return out[:, None], new_state
