"""TPU Pallas flash attention: causal GQA with optional sliding window.

Forward + backward (dq, dk, dv) kernels with explicit BlockSpec VMEM tiling.
Layouts: q (B, H, Sq, D), k/v (B, KVH, Skv, D); H = KVH * G.

TPU adaptation notes (vs the CUDA flash-attention algorithm):
* the KV loop is the *minor grid dimension* — TPU grids iterate the minor dim
  sequentially per core, so the (m, l, acc) online-softmax state lives in VMEM
  scratch that persists across KV iterations (no atomics / shared memory);
* a grid step has a fixed cost (its bookkeeping and block copies) of the
  order of the work in a 128 x 128 block, so blocks are chosen from the
  shapes (``choose_blocks``): a few hundred rows each, a multiple of 128;
* fully-masked causal blocks are predicated off with ``pl.when`` rather than
  skipped via grid surgery, and their index maps repeat the nearest live
  block's index, so Pallas copies nothing for them.

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

TILE = 128                    # MXU width; blocks of longer sequences are multiples
BLOCK_Q, BLOCK_K = 512, 1024  # the largest blocks chosen (v5e sweep, PERF.md)
SCOPED_VMEM = 16 * 2**20      # Mosaic's default scoped VMEM limit (v5e)


def _mm(a, b, contract):
    """Contract ``a`` with ``b`` on the MXU in their own dtype, accumulating
    in f32.  f32 operands ask for full precision: left to Mosaic's default,
    a pass may round them to bf16."""
    prec = jax.lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=prec,
                               preferred_element_type=jnp.float32)


# ------------------------------------------------------------- block choice

def _round_up(n, unit):
    return -(-n // unit) * unit


def _fit(length, target):
    """The block that covers ``length`` in ceil(length / target) even pieces,
    rounded up to the 128-row tile (to 16 rows for a shorter length)."""
    n = -(-length // target)
    return _round_up(-(-length // n), TILE if length > TILE else 16)


def vmem_bytes(block_q, block_k, d, itemsize=2):
    """VMEM the largest of the three kernels holds at these blocks: every
    in and out block twice (double-buffered), the f32 accumulators, the f32
    row statistics (one lane-padded column each), and six f32 (bq, bk)
    temporaries (s, p, dp, ds and the mask's two position grids)."""
    lanes = _round_up(d, TILE)
    big = max(block_q, block_k)
    blocks = 2 * itemsize * lanes * (2 * block_q + 2 * block_k + 2 * big)
    stats = 2 * 2 * 4 * TILE * block_q
    acc = 2 * 4 * lanes * big
    return blocks + stats + acc + 6 * 4 * block_q * block_k


def choose_blocks(sq, skv):
    """``(block_q, block_k)`` for attention of ``sq`` queries over ``skv``
    keys: up to ``BLOCK_Q`` x ``BLOCK_K``, split evenly over the lengths.
    At head dims up to 256 the kernels then need under 32 MiB of VMEM
    (``vmem_bytes``), a quarter of v5e's."""
    return _fit(sq, BLOCK_Q), _fit(skv, BLOCK_K)


def _blocks(sq, skv, block_q, block_k):
    """The caller's blocks (each capped at its length), else the chosen."""
    cq, ck = choose_blocks(sq, skv)
    return (cq if block_q is None else min(block_q, sq),
            ck if block_k is None else min(block_k, skv))


def _compiler_params(bq, bk, d, itemsize):
    """A scoped VMEM limit from the blocks, where the default is too small."""
    need = vmem_bytes(bq, bk, d, itemsize)
    if need <= SCOPED_VMEM:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=need + need // 4)


# ------------------------------------------------------------ block geometry

def live_k_range(qi, *, block_q, block_k, n_k, window, causal_shift):
    """First and last KV block that query block ``qi`` attends to."""
    r0 = qi * block_q + causal_shift            # absolute position, first row
    last = jnp.minimum(jnp.maximum(r0 + block_q - 1, 0) // block_k, n_k - 1)
    if window is None:
        return 0, last
    first = jnp.minimum(jnp.maximum(r0 - window + 1, 0) // block_k, n_k - 1)
    return first, last


def live_q_range(ki, *, block_q, block_k, n_q, window, causal_shift):
    """First and last query block that attends to KV block ``ki``."""
    c0 = ki * block_k
    first = jnp.minimum(jnp.maximum(c0 - causal_shift, 0) // block_q, n_q - 1)
    if window is None:
        return first, n_q - 1
    last = jnp.minimum(
        jnp.maximum(c0 + block_k - 2 - causal_shift + window, 0) // block_q,
        n_q - 1)
    return first, last


def _clamp(i, lo, hi):
    return jnp.minimum(jnp.maximum(i, lo), hi)


def _block_live(qi, ki, *, block_q, block_k, window, causal_shift, **_):
    """Whether block (qi, ki) keeps any (query, key) pair under the causal
    mask (and the window)."""
    r0 = qi * block_q + causal_shift            # absolute position, first row
    c0 = ki * block_k
    live = c0 <= r0 + block_q - 1
    if window is not None:
        live &= c0 + block_k - 1 > r0 - window
    return live


def _mask(qi, ki, *, block_q, block_k, sq_valid, skv_valid, window,
          causal_shift):
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    # causal_shift aligns q row i with absolute position i + causal_shift
    q_abs = q_pos + causal_shift
    mask = (k_pos <= q_abs) & (q_pos < sq_valid) & (k_pos < skv_valid)
    if window is not None:
        mask &= k_pos > q_abs - window
    return mask


def _scores(q, k, qi, ki, geom):
    """Block (qi, ki)'s scaled scores, NEG_INF where the mask drops them."""
    s = _mm(q, k, ((1,), (1,))) * (1.0 / np.sqrt(q.shape[-1]))
    return jnp.where(_mask(qi, ki, **geom), s, NEG_INF)


# ------------------------------------------------------------------- forward

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, n_kv_blocks, **geom):
    """Grid: (B, H, nQ, nKV) — nKV minor (sequential)."""
    ki = pl.program_id(3)
    qi = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_block_live(qi, ki, **geom))
    def _compute():
        q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
        s = _scores(q, k, qi, ki, geom)
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


def _pad(x, n):
    return jnp.pad(x, ((0, 0), (0, 0), (0, n - x.shape[2]), (0, 0)))


def flash_attention_fwd(q, k, v, *, window=None, causal_shift=0,
                        block_q=None, block_k=None, interpret=False):
    """q: (B,H,Sq,D); k,v: (B,KVH,Skv,D). Returns (o, lse).  Blocks left
    ``None`` are chosen from the shapes (``choose_blocks``)."""
    B, H, Sq, D = q.shape
    KVH, Skv = k.shape[1], k.shape[2]
    G = H // KVH
    bq, bk = _blocks(Sq, Skv, block_q, block_k)
    nq, nk = -(-Sq // bq), -(-Skv // bk)
    geom = dict(block_q=bq, block_k=bk, sq_valid=Sq, skv_valid=Skv,
                window=window, causal_shift=causal_shift)
    live_k = functools.partial(live_k_range, block_q=bq, block_k=bk, n_k=nk,
                               window=window, causal_shift=causal_shift)

    def q_map(b, h, qi, ki):
        return (b, h, qi, 0)

    def kv_map(b, h, qi, ki):
        return (b, h // G, _clamp(ki, *live_k(qi)), 0)

    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, n_kv_blocks=nk, **geom),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), q_map),
            pl.BlockSpec((1, 1, bk, D), kv_map),
            pl.BlockSpec((1, 1, bk, D), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, D), q_map),
            pl.BlockSpec((1, 1, bq, 1), q_map),
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
        compiler_params=_compiler_params(bq, bk, D, q.dtype.itemsize),
        interpret=interpret,
        name="flash_fwd",
    )(_pad(q, nq * bq), _pad(k, nk * bk), _pad(v, nk * bk))
    return o[:, :, :Sq], lse[:, :, :Sq, 0]


# ------------------------------------------------------------------ backward

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc, *, n_kv_blocks, **geom):
    ki = pl.program_id(3)
    qi = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(_block_live(qi, ki, **geom))
    def _compute():
        q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        scale = 1.0 / np.sqrt(q.shape[-1])
        p = jnp.exp(_scores(q, k, qi, ki, geom) - lse_ref[0, 0])
        dp = _mm(do, v, ((1,), (1,)))
        ds = p * (dp - delta_ref[0, 0]) * scale
        dq_acc[...] += _mm(ds.astype(k.dtype), k, ((1,), (0,)))

    @pl.when(ki == n_kv_blocks - 1)
    def _finish():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, n_q_blocks, n_g,
                    **geom):
    """Grid: (B, KVH, nK, G*nQ) — inner loop over (g, qi) accumulates dk/dv."""
    inner = pl.program_id(3)
    ki = pl.program_id(2)
    qi = inner % n_q_blocks

    @pl.when(inner == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(_block_live(qi, ki, **geom))
    def _compute():
        q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        scale = 1.0 / np.sqrt(q.shape[-1])
        p = jnp.exp(_scores(q, k, qi, ki, geom) - lse_ref[0, 0])   # (bq, bk)
        dv_acc[...] += _mm(p.astype(do.dtype), do, ((0,), (0,)))
        dp = _mm(do, v, ((1,), (1,)))
        ds = p * (dp - delta_ref[0, 0]) * scale                   # (bq, bk)
        dk_acc[...] += _mm(ds.astype(q.dtype), q, ((0,), (0,)))

    @pl.when(inner == n_g * n_q_blocks - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, o, lse, do, *, window=None, causal_shift=0,
                        block_q=None, block_k=None, interpret=False):
    B, H, Sq, D = q.shape
    KVH, Skv = k.shape[1], k.shape[2]
    G = H // KVH
    bq, bk = _blocks(Sq, Skv, block_q, block_k)
    nq, nk = -(-Sq // bq), -(-Skv // bk)
    qp, dop = _pad(q, nq * bq), _pad(do, nq * bq)
    kp, vp = _pad(k, nk * bk), _pad(v, nk * bk)
    # row statistics travel as (.., S, 1) columns: a block's last two dims
    # must tile (8, 128) or span the array, and a 1-wide last dim spans it
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    lsep, deltap = (_pad(x[..., None], nq * bq) for x in (lse, delta))

    geom = dict(block_q=bq, block_k=bk, sq_valid=Sq, skv_valid=Skv,
                window=window, causal_shift=causal_shift)
    params = _compiler_params(bq, bk, D, q.dtype.itemsize)
    live_k = functools.partial(live_k_range, block_q=bq, block_k=bk, n_k=nk,
                               window=window, causal_shift=causal_shift)
    live_q = functools.partial(live_q_range, block_q=bq, block_k=bk, n_q=nq,
                               window=window, causal_shift=causal_shift)

    def row_map(b, h, qi, ki):
        return (b, h, qi, 0)

    def kv_map(b, h, qi, ki):
        return (b, h // G, _clamp(ki, *live_k(qi)), 0)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, n_kv_blocks=nk, **geom),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), row_map),
            pl.BlockSpec((1, 1, bk, D), kv_map),
            pl.BlockSpec((1, 1, bk, D), kv_map),
            pl.BlockSpec((1, 1, bq, D), row_map),
            pl.BlockSpec((1, 1, bq, 1), row_map),
            pl.BlockSpec((1, 1, bq, 1), row_map),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), row_map),
        out_shape=jax.ShapeDtypeStruct((B, H, nq * bq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
        name="flash_bwd_dq",
    )(qp, kp, vp, dop, lsep, deltap)

    def q_map(b, kh, ki, i):
        return (b, kh * G + i // nq, _clamp(i % nq, *live_q(ki)), 0)

    def own_map(b, kh, ki, i):
        return (b, kh, ki, 0)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, n_q_blocks=nq, n_g=G, **geom),
        grid=(B, KVH, nk, G * nq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), q_map),
            pl.BlockSpec((1, 1, bk, D), own_map),
            pl.BlockSpec((1, 1, bk, D), own_map),
            pl.BlockSpec((1, 1, bq, D), q_map),
            pl.BlockSpec((1, 1, bq, 1), q_map),
            pl.BlockSpec((1, 1, bq, 1), q_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, D), own_map),
            pl.BlockSpec((1, 1, bk, D), own_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, KVH, nk * bk, D), k.dtype),
            jax.ShapeDtypeStruct((B, KVH, nk * bk, D), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qp, kp, vp, dop, lsep, deltap)
    return dq[:, :, :Sq], dk[:, :, :Skv], dv[:, :, :Skv]


# ------------------------------------------------------- custom_vjp assembly

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, window=None, causal_shift=0, block_q=None,
                    block_k=None, interpret=False):
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
