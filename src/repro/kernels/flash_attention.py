"""TPU Pallas flash attention: causal GQA with optional sliding window.

Forward + backward (dq, dk, dv) kernels with explicit BlockSpec VMEM tiling.
Layouts: q (B, H, Sq, D), k/v (B, KVH, Skv, D); H = KVH * G.

TPU adaptation notes (vs the CUDA flash-attention algorithm):
* the KV loop is the *minor grid dimension* — TPU grids iterate the minor dim
  sequentially per core, so the (m, l, acc) online-softmax state lives in VMEM
  scratch that persists across KV iterations (no atomics / shared memory);
* block shapes keep the MXU dims (block_q × D and block_k × D) multiples of
  128 where the model dims allow;
* fully-masked causal blocks are predicated off with ``pl.when`` rather than
  skipped via grid surgery.

Validated against ``ref.flash_attention_ref``, in interpret mode on the CPU
and compiled by Mosaic on the chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.3819763e38


def _mm(a, b, contract):
    """Contract ``a`` with ``b`` on the MXU in their own dtype, accumulating
    in f32.  f32 operands ask for full precision: left to Mosaic's default,
    a pass may round them to bf16."""
    prec = jax.lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=prec,
                               preferred_element_type=jnp.float32)


# ------------------------------------------------------------------- forward

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, block_q, block_k, n_kv_blocks, sq_valid, skv_valid,
                window, causal_shift):
    """Grid: (B, H, nQ, nKV) — nKV minor (sequential)."""
    ki = pl.program_id(3)
    qi = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    # causal_shift aligns q row i with absolute position i + causal_shift
    q_abs = q_pos + causal_shift
    mask = (k_pos <= q_abs) & (q_pos < sq_valid) & (k_pos < skv_valid)
    if window is not None:
        mask &= k_pos > q_abs - window

    block_live = (ki * block_k <= qi * block_q + causal_shift + block_q - 1)
    if window is not None:
        block_live &= ((ki + 1) * block_k - 1
                       > qi * block_q + causal_shift - window)

    @pl.when(block_live)
    def _compute():
        q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
        s = _mm(q, k, ((1,), (1,))) * (1.0 / np.sqrt(q.shape[-1]))
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                                 # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + _mm(p.astype(v.dtype), v,
                                                 ((1,), (0,)))
        m_ref[...] = m_new

    @pl.when(ki == n_kv_blocks - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[...] + jnp.log(l)


def flash_attention_fwd(q, k, v, *, window=None, causal_shift=0,
                        block_q=128, block_k=128, interpret=False):
    """q: (B,H,Sq,D); k,v: (B,KVH,Skv,D). Returns (o, lse)."""
    B, H, Sq, D = q.shape
    KVH, Skv = k.shape[1], k.shape[2]
    G = H // KVH
    bq, bk = min(block_q, Sq), min(block_k, Skv)
    nq, nk = -(-Sq // bq), -(-Skv // bk)
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, nq * bq - Sq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, nk * bk - Skv), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, nk * bk - Skv), (0, 0)))

    kernel = functools.partial(
        _fwd_kernel, block_q=bq, block_k=bk, n_kv_blocks=nk,
        sq_valid=Sq, skv_valid=Skv, window=window, causal_shift=causal_shift)
    grid = (B, H, nq, nk)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, qi, ki: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, qi, ki: (b, h // G, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, qi, ki: (b, h, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nq * bq, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, nq * bq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),   # acc
            pltpu.VMEM((bq, 1), jnp.float32),   # m
            pltpu.VMEM((bq, 1), jnp.float32),   # l
        ],
        interpret=interpret,
        name="flash_fwd",
    )(qp, kp, vp)
    return o[:, :, :Sq], lse[:, :, :Sq, 0]


# ------------------------------------------------------------------ backward

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc, *, block_q, block_k, n_kv_blocks, sq_valid,
                   skv_valid, window, causal_shift):
    ki = pl.program_id(3)
    qi = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    q_abs = q_pos + causal_shift
    mask = (k_pos <= q_abs) & (q_pos < sq_valid) & (k_pos < skv_valid)
    if window is not None:
        mask &= k_pos > q_abs - window
    block_live = (ki * block_k <= qi * block_q + causal_shift + block_q - 1)
    if window is not None:
        block_live &= ((ki + 1) * block_k - 1
                       > qi * block_q + causal_shift - window)

    @pl.when(block_live)
    def _compute():
        q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        scale = 1.0 / np.sqrt(q.shape[-1])
        s = _mm(q, k, ((1,), (1,))) * scale
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = _mm(do, v, ((1,), (1,)))
        ds = p * (dp - delta) * scale
        dq_acc[...] += _mm(ds.astype(k.dtype), k, ((1,), (0,)))

    @pl.when(ki == n_kv_blocks - 1)
    def _finish():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, block_q, block_k,
                    n_q_blocks, n_g, sq_valid, skv_valid, window, causal_shift):
    """Grid: (B, KVH, nK, G*nQ) — inner loop over (g, qi) accumulates dk/dv."""
    inner = pl.program_id(3)
    ki = pl.program_id(2)
    qi = inner % n_q_blocks

    @pl.when(inner == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    q_abs = q_pos + causal_shift
    mask = (k_pos <= q_abs) & (q_pos < sq_valid) & (k_pos < skv_valid)
    if window is not None:
        mask &= k_pos > q_abs - window
    block_live = (ki * block_k <= qi * block_q + causal_shift + block_q - 1)
    if window is not None:
        block_live &= ((ki + 1) * block_k - 1
                       > qi * block_q + causal_shift - window)

    @pl.when(block_live)
    def _compute():
        q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        scale = 1.0 / np.sqrt(q.shape[-1])
        s = _mm(q, k, ((1,), (1,))) * scale
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)                                # (bq, bk)
        dv_acc[...] += _mm(p.astype(do.dtype), do, ((0,), (0,)))
        dp = _mm(do, v, ((1,), (1,)))
        ds = p * (dp - delta) * scale                       # (bq, bk)
        dk_acc[...] += _mm(ds.astype(q.dtype), q, ((0,), (0,)))

    @pl.when(inner == n_g * n_q_blocks - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, o, lse, do, *, window=None, causal_shift=0,
                        block_q=128, block_k=128, interpret=False):
    B, H, Sq, D = q.shape
    KVH, Skv = k.shape[1], k.shape[2]
    G = H // KVH
    bq, bk = min(block_q, Sq), min(block_k, Skv)
    nq, nk = -(-Sq // bq), -(-Skv // bk)
    pad_q = nq * bq - Sq
    pad_k = nk * bk - Skv
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    dop = jnp.pad(do, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    # row statistics travel as (.., S, 1) columns: a block's last two dims
    # must tile (8, 128) or span the array, and a 1-wide last dim spans it
    lsep = jnp.pad(lse, ((0, 0), (0, 0), (0, pad_q)))[..., None]
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    deltap = jnp.pad(delta, ((0, 0), (0, 0), (0, pad_q)))[..., None]

    common = dict(block_q=bq, block_k=bk, sq_valid=Sq, skv_valid=Skv,
                  window=window, causal_shift=causal_shift)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, n_kv_blocks=nk, **common),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, qi, ki: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, qi, ki: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, bq, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, qi, ki: (b, h, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nq * bq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(qp, kp, vp, dop, lsep, deltap)

    def _q_map(b, kh, ki, i):
        return (b, kh * G + i // nq, i % nq, 0)


    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, n_q_blocks=nq, n_g=G, **common),
        grid=(B, KVH, nk, G * nq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), _q_map),
            pl.BlockSpec((1, 1, bk, D), lambda b, kh, ki, i: (b, kh, ki, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, kh, ki, i: (b, kh, ki, 0)),
            pl.BlockSpec((1, 1, bq, D), _q_map),
            pl.BlockSpec((1, 1, bq, 1), _q_map),
            pl.BlockSpec((1, 1, bq, 1), _q_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, D), lambda b, kh, ki, i: (b, kh, ki, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, kh, ki, i: (b, kh, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, KVH, nk * bk, D), k.dtype),
            jax.ShapeDtypeStruct((B, KVH, nk * bk, D), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qp, kp, vp, dop, lsep, deltap)
    return dq[:, :, :Sq], dk[:, :, :Skv], dv[:, :, :Skv]


# ------------------------------------------------------- custom_vjp assembly

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, window=None, causal_shift=0, block_q=128,
                    block_k=128, interpret=False):
    o, _ = flash_attention_fwd(q, k, v, window=window,
                               causal_shift=causal_shift, block_q=block_q,
                               block_k=block_k, interpret=interpret)
    return o


def _fa_fwd(q, k, v, window, causal_shift, block_q, block_k, interpret):
    o, lse = flash_attention_fwd(q, k, v, window=window,
                                 causal_shift=causal_shift, block_q=block_q,
                                 block_k=block_k, interpret=interpret)
    return o, (q, k, v, o, lse)


def _fa_bwd(window, causal_shift, block_q, block_k, interpret, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = flash_attention_bwd(q, k, v, o, lse, do, window=window,
                                     causal_shift=causal_shift,
                                     block_q=block_q, block_k=block_k,
                                     interpret=interpret)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


flash_attention.defvjp(_fa_fwd, _fa_bwd)
