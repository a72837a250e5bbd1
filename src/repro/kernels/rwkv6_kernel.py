"""TPU Pallas chunk-parallel RWKV-6 WKV with data-dependent per-channel decay.

Per head, per chunk of length C (state S0 carried in VMEM scratch across the
sequential minor grid dim):

    lp      = cumsum(w_log)                      (C, hs) inclusive, chunk-local
    o_t     = (r_t * exp(lp_{t-1})) @ S0                        [inter-chunk]
            + sum_c r[t,c] k[s,c] exp(lp[t-1,c]-lp[s,c])  v_s   [intra, s<t]
            + (r_t . (u * k_t)) v_t                             [bonus diag]
    S_new   = diag(exp(lp_C)) S0 + (k * exp(lp_C - lp))^T @ v

(the kernel keeps S transposed, v-major, so the decay scales lanes).

All exp arguments are <= 0 (decay in (0,1)) so the chunked form is
numerically safe; underflow of exp(lp) only zeroes already-decayed state.
This is the standard chunked gated-linear-attention factorization (GLA /
fla-style) adapted to TPU: the (C, C, hs) pairwise-decay tensor lives in
VMEM (C=64, hs=64 -> 1 MiB f32) and feeds the MXU via two batched dots.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _mm


def _rwkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_ref, *, chunk):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0, 0].astype(jnp.float32)           # (C, hs)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    w_log = w_ref[0, 0].astype(jnp.float32)       # (C, hs), <= 0
    u = u_ref[0].astype(jnp.float32)              # (1, hs)
    S0 = s_ref[...]                               # (hs_v, hs_k) v-major

    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # inclusive cumsum over the chunk as a lower-triangular matmul (Mosaic
    # has no cumsum)
    tri = (s_idx <= t_idx).astype(jnp.float32)
    lp = _mm(tri, w_log, ((1,), (0,)))
    lp_prev = lp - w_log                          # exclusive

    # inter-chunk: query the carried state
    q_dec = r * jnp.exp(lp_prev)                  # (C, hs_k)
    o = _mm(q_dec, S0, ((1,), (1,)))

    # intra-chunk: pairwise decay attention (strictly lower triangular)
    ddiff = lp_prev[:, None, :] - lp[None, :, :]  # (C, C, hs); <=0 for s<t
    pair = r[:, None, :] * k[None, :, :] * jnp.exp(jnp.minimum(ddiff, 0.0))
    A = pair.sum(axis=-1)                         # (C, C)
    A = jnp.where(s_idx < t_idx, A, 0.0)
    # bonus diagonal
    bonus = (r * u * k).sum(axis=-1, keepdims=True)   # (C, 1)
    A = A + jnp.where(s_idx == t_idx, bonus, 0.0)
    o = o + _mm(A, v, ((1,), (0,)))
    o_ref[0, 0] = o.astype(o_ref.dtype)

    # state update: S^T <- S^T diag(exp(lp_C)) + v^T k_hat
    lpC = lp[chunk - 1:chunk]                     # (1, hs_k)
    k_hat = k * jnp.exp(lpC - lp)                 # (C, hs_k)
    s_ref[...] = S0 * jnp.exp(lpC) + _mm(v, k_hat, ((0,), (0,)))


def rwkv6_wkv(r, k, v, w_log, u, *, chunk=64, interpret=False):
    """r,k,v,w_log: (B, H, S, hs); u: (H, hs). Returns o: (B, H, S, hs) f32.

    The carried state is kept v-major (its transpose) so that the decay
    scales lanes, and ``u`` travels as (H, 1, hs) so that its block spans
    the array's last two dims.
    """
    B, H, S, hs = r.shape
    C = min(chunk, S)
    nc = -(-S // C)
    pad = nc * C - S
    padder = lambda x: jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
    rp, kp, vp = padder(r), padder(k), padder(v)
    wp = jnp.pad(w_log, ((0, 0), (0, 0), (0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_rwkv6_kernel, chunk=C),
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, C, hs), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, C, hs), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, C, hs), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, C, hs), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, hs), lambda b, h, ci: (h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, C, hs), lambda b, h, ci: (b, h, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nc * C, hs), jnp.float32),
        scratch_shapes=[pltpu.VMEM((hs, hs), jnp.float32)],
        interpret=interpret,
        name="rwkv6_scan",
    )(rp, kp, vp, wp, u[:, None, :])
    return out[:, :, :S]
