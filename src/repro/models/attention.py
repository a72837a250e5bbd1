"""GQA/MQA/MHA attention with RoPE, optional QKV bias, sliding window, KV cache.

Three execution paths (all numerically equivalent where applicable):

* ``plain``    — materializes (Sq, Skv) scores; used for training at moderate
                 seq (grads are simple; remat recomputes in bwd).
* ``blocked``  — online-softmax scan over KV blocks, O(S) live memory; used for
                 long prefill.  Also serves as the pure-jnp oracle for the
                 Pallas flash-attention kernel.
* ``local``    — chunked sliding-window attention (self + previous chunk),
                 O(S·W) FLOPs; used by window archs (recurrentgemma, mixtral)
                 at long sequence.

Decode attends one query token against a (possibly ring-buffered) cache.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .module import ParamSpec
from .layers import apply_rope
from ..launch.sharding import maybe_constrain

NEG_INF = -2.3819763e38  # large negative for masking (bf16-safe)


def attn_specs(d_model: int, n_heads: int, n_kv: int, d_head: int, bias: bool):
    s = {
        "wq": ParamSpec((d_model, n_heads, d_head), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((d_model, n_kv, d_head), ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((d_model, n_kv, d_head), ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((n_heads, d_head, d_model), ("heads", "head_dim", "embed")),
    }
    if bias:
        s["bq"] = ParamSpec((n_heads, d_head), ("heads", "head_dim"), "zeros")
        s["bk"] = ParamSpec((n_kv, d_head), ("kv_heads", "head_dim"), "zeros")
        s["bv"] = ParamSpec((n_kv, d_head), ("kv_heads", "head_dim"), "zeros")
    return s


def qkv_proj(p, x, n_heads, n_kv, d_head, positions, rope_theta, use_rope=True):
    """x: (B,S,D) -> q (B,S,KV,G,dh), k,v (B,S,KV,dh); RoPE applied."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if "bq" in p:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    if use_rope:
        q = apply_rope(q.swapaxes(1, 2), positions[:, None, :], rope_theta).swapaxes(1, 2)
        k = apply_rope(k.swapaxes(1, 2), positions[:, None, :], rope_theta).swapaxes(1, 2)
    g = n_heads // n_kv
    B, S = x.shape[:2]
    q = q.reshape(B, S, n_kv, g, d_head)
    return q, k, v


def _softmax_f32(scores, axis=-1):
    return jax.nn.softmax(scores.astype(jnp.float32), axis=axis)


def plain_attention(q, k, v, positions_q, positions_kv, window=None):
    """q: (B,Sq,KV,G,dh); k,v: (B,Skv,KV,dh). Causal (+ optional window)."""
    dh = q.shape[-1]
    scores = jnp.einsum("bqkgd,btkd->bkgqt", q, k) / np.sqrt(dh)
    pq = positions_q[:, None, None, :, None]
    pt = positions_kv[:, None, None, None, :]
    mask = pt <= pq
    if window is not None:
        mask &= pt > pq - window
    scores = jnp.where(mask, scores.astype(jnp.float32), NEG_INF)
    w = _softmax_f32(scores)
    out = jnp.einsum("bkgqt,btkd->bqkgd", w.astype(v.dtype), v)
    return out


def blocked_attention(q, k, v, positions_q, positions_kv, window=None, block=None):
    """Online-softmax over KV blocks (flash-attention algebra, pure jnp).

    Default block scales with Skv: fewer KV iterations means fewer HBM
    spills of the (m, l, acc) carry in the XLA-scan fallback (the Pallas
    kernel keeps the carry in VMEM; this narrows the gap).
    """
    B, Sq, KV, G, dh = q.shape
    Skv = k.shape[1]
    if block is None:
        block = max(512, min(4096, Skv // 8))
    nb = -(-Skv // block)
    pad = nb * block - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        positions_kv = jnp.pad(positions_kv, ((0, 0), (0, pad)),
                               constant_values=2**30)
    kb = k.reshape(B, nb, block, KV, dh).swapaxes(0, 1)
    vb = v.reshape(B, nb, block, KV, dh).swapaxes(0, 1)
    pb = positions_kv.reshape(B, nb, block).swapaxes(0, 1)
    scale = 1.0 / np.sqrt(dh)
    pq = positions_q[:, None, None, :, None]                       # (B,1,1,Sq,1)

    m0 = jnp.full((B, KV, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, KV, G, Sq, dh), jnp.float32)

    def step(carry, blk):
        m, l, acc = carry
        kk, vv, pkv = blk
        s = jnp.einsum("bqkgd,btkd->bkgqt", q, kk).astype(jnp.float32) * scale
        pt = pkv[:, None, None, None, :]
        mask = pt <= pq
        if window is not None:
            mask &= pt > pq - window
        s = jnp.where(mask, s, NEG_INF)
        # the running max only shifts the exponent, so the result does not
        # depend on it and it carries no gradient (as in jax.nn.softmax).
        # Left differentiable, its gradient divides by the number of scores
        # equal to the max, and on the TPU in bf16 dq and dk came out NaN.
        m_new = jax.lax.stop_gradient(jnp.maximum(m, s.max(axis=-1)))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgqt,btkd->bkgqd", p, vv.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kb, vb, pb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).astype(v.dtype)  # (B,Sq,KV,G,dh)


def local_chunk_attention(q, k, v, positions_q, positions_kv, window):
    """Exact sliding-window attention via self+previous chunks. O(S·2W·d)."""
    B, S, KV, G, dh = q.shape
    C = window
    nc = -(-S // C)
    pad = nc * C - S
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        positions_q = jnp.pad(positions_q, ((0, 0), (0, pad)), constant_values=-(2**30))
        positions_kv = jnp.pad(positions_kv, ((0, 0), (0, pad)), constant_values=2**30)
    qc = q.reshape(B, nc, C, KV, G, dh)
    kc = k.reshape(B, nc, C, KV, dh)
    vc = v.reshape(B, nc, C, KV, dh)
    pqc = positions_q.reshape(B, nc, C)
    pkc = positions_kv.reshape(B, nc, C)
    # previous chunk (zero for the first)
    kp = jnp.concatenate([jnp.zeros_like(kc[:, :1]), kc[:, :-1]], axis=1)
    vp = jnp.concatenate([jnp.zeros_like(vc[:, :1]), vc[:, :-1]], axis=1)
    pkp = jnp.concatenate([jnp.full_like(pkc[:, :1], 2**30), pkc[:, :-1]], axis=1)
    kk = jnp.concatenate([kp, kc], axis=2)          # (B,nc,2C,KV,dh)
    vv = jnp.concatenate([vp, vc], axis=2)
    pk = jnp.concatenate([pkp, pkc], axis=2)        # (B,nc,2C)
    s = jnp.einsum("bnqkgd,bntkd->bnkgqt", qc, kk).astype(jnp.float32) / np.sqrt(dh)
    pq = pqc[:, :, None, None, :, None]
    pt = pk[:, :, None, None, None, :]
    mask = (pt <= pq) & (pt > pq - window)
    s = jnp.where(mask, s, NEG_INF)
    w = _softmax_f32(s)
    out = jnp.einsum("bnkgqt,bntkd->bnqkgd", w.astype(vv.dtype), vv)
    out = out.reshape(B, nc * C, KV, G, dh)
    return out[:, :S]


def cache_shapes(batch, cache_len, n_kv, d_head, dtype):
    return {
        "k": jax.ShapeDtypeStruct((batch, cache_len, n_kv, d_head), dtype),
        "v": jax.ShapeDtypeStruct((batch, cache_len, n_kv, d_head), dtype),
        "pos": jax.ShapeDtypeStruct((batch, cache_len), jnp.int32),
    }


CACHE_AXES = {"k": ("batch", "cache_seq", "kv_heads", "head_dim"),
              "v": ("batch", "cache_seq", "kv_heads", "head_dim"),
              "pos": ("batch", "cache_seq")}


def _write_rows(cache, slot, rows):
    """Each lane's new key, value and position into its one row
    ``(b, slot[b])`` of the cache.  With the state donated the scatter
    writes the cache in place, and attention reads the same buffer; under
    cache_seq sharding each shard writes the rows that fall in it."""
    lane = jnp.arange(slot.shape[0])
    if slot.shape[0] == 1:
        # XLA rewrites a scatter of one row as a dynamic-update-slice, which
        # the partitioner applies to the whole gathered cache when its
        # sequence axis is sharded; a scatter of the row twice stays a
        # scatter, on the shard that holds the row
        lane = jnp.concatenate([lane, lane])
        slot = jnp.concatenate([slot, slot])
        rows = {n: jnp.concatenate([r, r]) for n, r in rows.items()}
    return {n: cache[n].at[lane, slot].set(rows[n].astype(cache[n].dtype))
            for n in cache}


def decode_attention(p, cache, x, position, *, n_heads, n_kv, d_head,
                     rope_theta, window=None, use_rope=True):
    """One-token decode. x: (B,1,D); position: (B,) int32 current index.

    Cache is a ring buffer when ``window`` is set (slot = pos % len), else a
    linear buffer (slot = pos).  K is stored post-RoPE.
    Returns (attn_out (B,1,KV,G,dh), new_cache).
    """
    T = cache["k"].shape[1]
    q, k, v = qkv_proj(p, x, n_heads, n_kv, d_head,
                       position[:, None], rope_theta, use_rope)
    slot = position % T if window is not None else jnp.minimum(position, T - 1)

    with jax.named_scope("kv_cache"):
        new_cache = _write_rows(cache, slot, {"k": k[:, 0], "v": v[:, 0],
                                              "pos": position})
    kk, vv, pos_kv = new_cache["k"], new_cache["v"], new_cache["pos"]
    g = n_heads // n_kv
    q = maybe_constrain(q, ("batch", None, "kv_heads", "heads", "head_dim"))
    s = jnp.einsum("bqkgd,btkd->bkgqt", q, kk).astype(jnp.float32) / np.sqrt(d_head)
    pq = position[:, None, None, None, None]
    pt = pos_kv[:, None, None, None, :]
    mask = (pt >= 0) & (pt <= pq)
    if window is not None:
        mask &= pt > pq - window
    s = jnp.where(mask, s, NEG_INF)
    w = _softmax_f32(s)
    out = jnp.einsum("bkgqt,btkd->bqkgd", w.astype(vv.dtype), vv)
    return out, new_cache


def out_proj(p, attn_out):
    """attn_out: (B,S,KV,G,dh) -> (B,S,D)."""
    B, S, KV, G, dh = attn_out.shape
    x = attn_out.reshape(B, S, KV * G, dh)
    return jnp.einsum("bshk,hkd->bsd", x, p["wo"])


def pallas_attention(q, k, v, window=None):
    """Dispatch (B,S,KV,G,dh) GQA tensors to the Pallas flash kernel."""
    from ..kernels import ops
    B, S, KV, G, dh = q.shape
    qk = q.transpose(0, 2, 3, 1, 4).reshape(B, KV * G, S, dh)
    kk = k.transpose(0, 2, 1, 3)
    vk = v.transpose(0, 2, 1, 3)
    o = ops.attention(qk, kk, vk, window=window, use_pallas=True)
    return o.reshape(B, KV, G, S, dh).transpose(0, 3, 1, 2, 4)


def full_attention(p, x, positions, *, n_heads, n_kv, d_head, rope_theta,
                   window=None, impl="plain", use_rope=True, block=512):
    """Full-sequence self-attention (train / prefill). Returns (B,S,D)."""
    q, k, v = qkv_proj(p, x, n_heads, n_kv, d_head, positions, rope_theta, use_rope)
    q = maybe_constrain(q, ("batch", "seq_q", "kv_heads", "heads", "head_dim"))
    k = maybe_constrain(k, ("batch", None, "kv_heads", "head_dim"))
    if impl == "pallas":
        o = pallas_attention(q, k, v, window)
    elif impl == "local" and window is not None and x.shape[1] > 2 * window:
        o = local_chunk_attention(q, k, v, positions, positions, window)
    elif impl == "blocked":
        o = blocked_attention(q, k, v, positions, positions, window, block=block)
    else:
        o = plain_attention(q, k, v, positions, positions, window)
    o = maybe_constrain(o, ("batch", "seq_q", "kv_heads", "heads", "head_dim"))
    return out_proj(p, o)
