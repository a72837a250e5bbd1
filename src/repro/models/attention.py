"""GQA/MQA/MHA attention with RoPE, optional QKV bias, sliding window, KV cache.

``self_attention`` is the one full-sequence path, for training and prefill;
``attend`` chooses how it attends (all numerically equivalent where
applicable):

* ``pallas``   — the Pallas flash kernel, under ``use_pallas``.
* ``plain``    — materializes (Sq, Skv) scores; used for training at moderate
                 seq (grads are simple; remat recomputes in bwd).
* ``blocked``  — online-softmax scan over KV blocks, O(S) live memory; used for
                 long prefill.  Also serves as the pure-jnp oracle for the
                 Pallas flash-attention kernel.
* ``local``    — chunked sliding-window attention (self + previous chunk),
                 O(S·W) FLOPs; used by window archs (recurrentgemma, mixtral)
                 at long sequence.

Decode attends one query token against the cache; ``cache_layout`` decides
the cache's rows and whether they are a ring, for the decode state, the
cache prefill builds and the decode step's writes.
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


def qkv_proj(p, x, positions, cfg):
    """x: (B,S,D) -> q (B,S,KV,G,dh), k,v (B,S,KV,dh); RoPE applied."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if "bq" in p:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    if cfg.use_rope:
        q = apply_rope(q.swapaxes(1, 2), positions[:, None, :], cfg.rope_theta).swapaxes(1, 2)
        k = apply_rope(k.swapaxes(1, 2), positions[:, None, :], cfg.rope_theta).swapaxes(1, 2)
    g = cfg.n_heads // cfg.n_kv_heads
    B, S = x.shape[:2]
    q = q.reshape(B, S, cfg.n_kv_heads, g, cfg.d_head)
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


def cache_layout(cache_len, window):
    """An attention layer's decode cache: ``(rows, ring)``.  A window
    architecture keeps a ring of ``min(cache_len, window)`` rows, position
    ``t`` in row ``t % rows``; otherwise ``cache_len`` rows in position
    order."""
    if window is None:
        return cache_len, False
    return min(cache_len, window), True


def cache_shapes(batch, cache_len, cfg, dtype):
    rows, _ = cache_layout(cache_len, cfg.window)
    kv = jax.ShapeDtypeStruct((batch, rows, cfg.n_kv_heads, cfg.d_head), dtype)
    return {"k": kv, "v": kv,
            "pos": jax.ShapeDtypeStruct((batch, rows), jnp.int32)}


CACHE_AXES = {"k": ("batch", "cache_seq", "kv_heads", "head_dim"),
              "v": ("batch", "cache_seq", "kv_heads", "head_dim"),
              "pos": ("batch", "cache_seq")}


@jax.named_scope("kv_cache")
def cache_from_kv(k, v, positions, cache_len, window):
    """The decode cache of prefilled sequences, laid out as ``cache_layout``
    says: a ring holds the last ``rows`` positions, each in its row; else
    the sequence is padded to the cache's rows.  k, v: (B,S,KV,dh)."""
    rows, ring = cache_layout(cache_len, window)
    B, S = k.shape[:2]
    if ring and S > rows:
        kk, vv, pos = k[:, -rows:], v[:, -rows:], positions[:, -rows:]
        slot = pos % rows
        bidx = jnp.arange(B)[:, None]
        ck = jnp.zeros((B, rows) + k.shape[2:], k.dtype).at[bidx, slot].set(kk)
        cv = jnp.zeros_like(ck).at[bidx, slot].set(vv)
        cp = jnp.full((B, rows), -1, jnp.int32).at[bidx, slot].set(pos)
        return {"k": ck, "v": cv, "pos": cp}
    pad = rows - S
    return {"k": jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))),
            "v": jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))),
            "pos": jnp.pad(positions, ((0, 0), (0, pad)), constant_values=-1)}


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


def decode_attention(p, cache, x, position, cfg):
    """One-token decode. x: (B,1,D); position: (B,) int32 current index.

    K is stored post-RoPE, in the row ``cache_layout`` gives the position.
    Returns (out (B,1,D), new_cache).
    """
    T = cache["k"].shape[1]
    q, k, v = qkv_proj(p, x, position[:, None], cfg)
    _, ring = cache_layout(T, cfg.window)
    slot = position % T if ring else jnp.minimum(position, T - 1)

    with jax.named_scope("kv_cache"):
        new_cache = _write_rows(cache, slot, {"k": k[:, 0], "v": v[:, 0],
                                              "pos": position})
    kk, vv, pos_kv = new_cache["k"], new_cache["v"], new_cache["pos"]
    q = maybe_constrain(q, ("batch", None, "kv_heads", "heads", "head_dim"))
    s = jnp.einsum("bqkgd,btkd->bkgqt", q, kk).astype(jnp.float32) / np.sqrt(cfg.d_head)
    pq = position[:, None, None, None, None]
    pt = pos_kv[:, None, None, None, :]
    mask = (pt >= 0) & (pt <= pq)
    if cfg.window is not None:
        mask &= pt > pq - cfg.window
    s = jnp.where(mask, s, NEG_INF)
    w = _softmax_f32(s)
    out = jnp.einsum("bkgqt,btkd->bqkgd", w.astype(vv.dtype), vv)
    return out_proj(p, out), new_cache


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


def attend(q, k, v, positions, window, policy):
    """Causal self-attention of a whole sequence, by the implementation the
    policy and the length choose: the flash kernel under ``use_pallas``;
    else ``policy.attn_impl``, where ``auto`` takes ``local`` for a window
    shorter than half the sequence, ``blocked`` from 2048 positions and
    ``plain`` below.  ``local`` applies only to such a window, and is
    ``plain`` otherwise."""
    if policy.use_pallas:
        return pallas_attention(q, k, v, window)
    S = q.shape[1]
    local = window is not None and S > 2 * window
    impl = policy.attn_impl
    if impl == "auto":
        impl = "local" if local else "blocked" if S >= 2048 else "plain"
    if impl == "local" and local:
        return local_chunk_attention(q, k, v, positions, positions, window)
    if impl == "blocked":
        return blocked_attention(q, k, v, positions, positions, window)
    return plain_attention(q, k, v, positions, positions, window)


def self_attention(p, x, positions, cfg, policy, cache_len=None):
    """Full-sequence causal self-attention (training, and prefill when
    ``cache_len`` is given).  x: (B,S,D) -> (out (B,S,D), the decode cache
    built from this pass's keys and values, or None)."""
    q, k, v = qkv_proj(p, x, positions, cfg)
    q = maybe_constrain(q, ("batch", "seq_q", "kv_heads", "heads", "head_dim"))
    k = maybe_constrain(k, ("batch", None, "kv_heads", "head_dim"))
    o = attend(q, k, v, positions, cfg.window, policy)
    o = maybe_constrain(o, ("batch", "seq_q", "kv_heads", "heads", "head_dim"))
    out = out_proj(p, o)
    if cache_len is None:
        return out, None
    return out, cache_from_kv(k, v, positions, cache_len, cfg.window)
