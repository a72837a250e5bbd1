"""TPU Pallas flash-decode: one query token vs a (ring-buffered) KV cache.

Layouts: q (B, H, D); k,v (B, KVH, T, D); cache positions pos (B, T) int32
(-1 = empty slot), query position qpos (B,).  The KV length is tiled as the
minor (sequential) grid dim with online-softmax state in VMEM scratch —
the TPU analogue of split-K flash-decoding (FlashDecoding++ adapted to the
sequential-minor-grid model; combination happens in scratch, not via a
second kernel pass).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _mm

NEG_INF = -2.3819763e38


def _decode_kernel(q_ref, k_ref, v_ref, pos_ref, qpos_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, block_k, n_kv_blocks, window):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]  # (1, D), (bk, D) x2
    pt = pos_ref[0]                                  # (1, bk)
    qpos = qpos_ref[0]                               # (1, 1)
    s = _mm(q, k, ((1,), (1,))) * (1.0 / np.sqrt(q.shape[-1]))   # (1, bk)
    mask = (pt >= 0) & (pt <= qpos)
    if window is not None:
        mask &= pt > qpos - window
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_ref[...]                              # (1, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + _mm(p.astype(v.dtype), v,
                                             ((1,), (0,)))
    m_ref[...] = m_new

    @pl.when(ki == n_kv_blocks - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                       ).astype(o_ref.dtype)


def flash_decode(q, k, v, pos, qpos, *, window=None, block_k=512,
                 interpret=False):
    """q: (B,H,D); k,v: (B,KVH,T,D); pos: (B,T) i32; qpos: (B,) i32.

    The query, positions and output travel with a unit axis inserted before
    their last dim: a block's last two dims must tile (8, 128) or span the
    array, and a unit dim spans it.
    """
    B, H, D = q.shape
    KVH, T = k.shape[1], k.shape[2]
    G = H // KVH
    bk = min(block_k, T)
    nk = -(-T // bk)
    padt = nk * bk - T
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, padt), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, padt), (0, 0)))
    posp = jnp.pad(pos, ((0, 0), (0, padt)), constant_values=-1)[:, None, :]
    qpos3 = qpos.astype(jnp.int32)[:, None, None]

    kernel = functools.partial(_decode_kernel, block_k=bk, n_kv_blocks=nk,
                               window=window)
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nk),
        in_specs=[
            pl.BlockSpec((1, 1, 1, D), lambda b, h, ki: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, ki: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, ki: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, h, ki: (b, 0, ki)),
            pl.BlockSpec((1, 1, 1), lambda b, h, ki: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, D), lambda b, h, ki: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, 1, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, D), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=interpret,
        name="decode_attn",
    )(q[:, :, None, :], kp, vp, posp, qpos3)
    return out[:, :, 0]
