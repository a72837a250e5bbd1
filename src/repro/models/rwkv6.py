"""RWKV-6 "Finch" block: time-mix (data-dependent decay WKV) + channel-mix.

Time-mix recurrence per head (state S in R^{hs x hs}, k-major):
    o_t = r_t @ (S_{t-1} + diag(u) k_t v_t^T)
    S_t = diag(w_t) S_{t-1} + k_t v_t^T
with per-channel data-dependent decay w_t = exp(-exp(w0 + lora(x_w))) in (0,1)
and data-dependent token-shift interpolation (ddlerp) for the five streams
(w,k,v,r,g), as in arXiv:2404.05892.

The full-sequence time-mix computes the recurrence by the TPU Pallas kernel
(``kernels/rwkv6_kernel.py``, chunk-parallel), by a chunked or a
sequence-parallel chunked formulation, or by a scan over time (the exact
oracle); all but the kernel also give the final state.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .module import ParamSpec
from ..launch.sharding import maybe_constrain

LORA_MIX = 32
LORA_DECAY = 64
FIVE = 5  # w,k,v,r,g


def timemix_specs(d: int, n_heads: int, head_size: int):
    return {
        "mu_x": ParamSpec((d,), ("embed",), "uniform_scale", 0.5),
        "mu": ParamSpec((FIVE, d), (None, "embed"), "uniform_scale", 0.5),
        "lora_A": ParamSpec((d, FIVE * LORA_MIX), ("embed", None)),
        "lora_B": ParamSpec((FIVE, LORA_MIX, d), (None, None, "embed"), "normal", 0.1),
        "w0": ParamSpec((d,), ("embed",), "uniform_scale", 2.0),
        "wA": ParamSpec((d, LORA_DECAY), ("embed", None)),
        "wB": ParamSpec((LORA_DECAY, d), (None, "embed"), "normal", 0.1),
        "u": ParamSpec((n_heads, head_size), ("rwkv_heads", "head_dim"),
                       "uniform_scale", 0.5),
        "wr": ParamSpec((d, n_heads, head_size), ("embed", "rwkv_heads", "head_dim")),
        "wk": ParamSpec((d, n_heads, head_size), ("embed", "rwkv_heads", "head_dim")),
        "wv": ParamSpec((d, n_heads, head_size), ("embed", "rwkv_heads", "head_dim")),
        "wg": ParamSpec((d, n_heads, head_size), ("embed", "rwkv_heads", "head_dim")),
        "ln_scale": ParamSpec((n_heads, head_size), ("rwkv_heads", "head_dim"), "ones"),
        "ln_bias": ParamSpec((n_heads, head_size), ("rwkv_heads", "head_dim"), "zeros"),
        "wo": ParamSpec((n_heads, head_size, d), ("rwkv_heads", "head_dim", "embed")),
    }


def channelmix_specs(d: int, f: int):
    return {
        "mu_k": ParamSpec((d,), ("embed",), "uniform_scale", 0.5),
        "mu_r": ParamSpec((d,), ("embed",), "uniform_scale", 0.5),
        "wk": ParamSpec((d, f), ("embed", "mlp")),
        "wv": ParamSpec((f, d), ("mlp", "embed")),
        "wr": ParamSpec((d, d), ("embed", None)),
    }


def _ddlerp(p, x, xx):
    """Data-dependent token-shift interpolation -> five mixed streams."""
    dx = xx - x
    xmx = x + dx * p["mu_x"].astype(x.dtype)
    lo = jnp.tanh(jnp.einsum("bsd,dl->bsl", xmx, p["lora_A"]))
    B, S = x.shape[:2]
    lo = lo.reshape(B, S, FIVE, LORA_MIX)
    adj = jnp.einsum("bsfl,fld->bsfd", lo, p["lora_B"])      # (B,S,5,D)
    mix = p["mu"].astype(x.dtype)[None, None] + adj
    return x[:, :, None, :] + dx[:, :, None, :] * mix        # (B,S,5,D)


def _wkv_scan(r, k, v, w_log, u):
    """Exact sequential WKV. r,k,v,w_log: (B,S,H,hs); u: (H,hs).

    Returns o (B,S,H,hs) f32 and the final state (B,H,hs,hs) f32.
    """
    B, S, H, hs = r.shape
    rf = r.astype(jnp.float32).swapaxes(0, 1)
    kf = k.astype(jnp.float32).swapaxes(0, 1)
    vf = v.astype(jnp.float32).swapaxes(0, 1)
    wf = jnp.exp(w_log.astype(jnp.float32)).swapaxes(0, 1)   # decay in (0,1)
    uf = u.astype(jnp.float32)

    def step(state, inp):
        rt, kt, vt, wt = inp                                  # (B,H,hs)
        kv = kt[..., :, None] * vt[..., None, :]              # (B,H,hs,hs)
        o = jnp.einsum("bhk,bhkv->bhv", rt, state + uf[None, :, :, None] * kv)
        state = wt[..., :, None] * state + kv
        return state, o

    s0 = jnp.zeros((B, H, hs, hs), jnp.float32)
    final, o = jax.lax.scan(step, s0, (rf, kf, vf, wf))
    return o.swapaxes(0, 1), final


def wkv_chunked(r, k, v, w_log, u, chunk: int = 16):
    """Chunk-parallel WKV (same algebra as kernels/rwkv6_kernel.py).

    State is touched once per chunk instead of once per token — ~chunk x less
    HBM traffic than the sequential scan (the §Perf lever for the rwkv cells).
    Intra-chunk attention uses the two-matmul factorization with per-chunk
    exponent centering: for s<t the decay exponent lp_prev[t]-lp[s] <= 0, and
    centering at the chunk midpoint bounds both factors' exponents by
    (chunk/2)*|w_log|, safe in f32 for chunk=16 at our decay scales.

    r,k,v,w_log: (B,S,H,hs); u: (H,hs) -> o (B,S,H,hs) f32 + final state.
    """
    B, S, H, hs = r.shape
    C = min(chunk, S)
    nc = -(-S // C)
    pad = nc * C - S

    def pad_t(x):
        return jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))

    dt = r.dtype            # streams stay in compute dtype (bf16-safe:
    rf = pad_t(r).reshape(B, nc, C, H, hs)      # bf16 shares f32's exponent)
    kf = pad_t(k).reshape(B, nc, C, H, hs)
    vf = pad_t(v).reshape(B, nc, C, H, hs)
    # pad decay with log(1)=0: padded steps must not decay the carried
    # state (k/v pads are zero, so they contribute nothing either)
    wf = jnp.pad(w_log.astype(jnp.float32), ((0, 0), (0, pad), (0, 0), (0, 0)),
                 constant_values=0.0).reshape(B, nc, C, H, hs)
    uf = u.astype(dt)
    # chunk-major for the scan
    rc, kc, vc, wc = (x.swapaxes(0, 1) for x in (rf, kf, vf, wf))

    t_idx = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    causal = (s_idx < t_idx)[None, None]                     # (1,1,C,C)

    f32 = jnp.float32

    def step(state, inp):
        rt, kt, vt, wt = inp                                 # (B,C,H,hs)
        lp = jnp.cumsum(wt, axis=1)                          # inclusive, f32
        lp_prev = lp - wt
        mid = lp[:, C // 2][:, None]                         # centering
        q_dec = rt * jnp.exp(lp_prev - mid).astype(dt)       # (B,C,H,hs)
        k_dec = kt * jnp.exp(mid - lp).astype(dt)
        # inter-chunk: query the carried state (f32 accumulate)
        o = jnp.einsum("bchk,bhkv->bchv",
                       (rt * jnp.exp(lp_prev).astype(dt)).astype(f32), state)
        # intra-chunk
        A = jnp.einsum("bthk,bshk->bhts", q_dec, k_dec,
                       preferred_element_type=f32)
        A = jnp.where(causal, A, 0.0)
        bonus = jnp.einsum("bthk,bthk->bth", rt * uf[None, None], kt,
                           preferred_element_type=f32)
        o = o + jnp.einsum("bhts,bshv->bthv", A.astype(dt), vt,
                           preferred_element_type=f32) \
            + bonus[..., None] * vt.astype(f32)
        # state update
        lpC = lp[:, -1][:, None]                             # (B,1,H,hs)
        k_hat = kt * jnp.exp(lpC - lp).astype(dt)
        state = jnp.exp(lpC[:, 0])[..., None] * state \
            + jnp.einsum("bchk,bchv->bhkv", k_hat, vt,
                         preferred_element_type=f32)
        return state, o

    s0 = jnp.zeros((B, H, hs, hs), jnp.float32)
    final, o = jax.lax.scan(step, s0, (rc, kc, vc, wc))
    o = o.swapaxes(0, 1).reshape(B, nc * C, H, hs)[:, :S]
    return o, final


def wkv_seq_parallel(r, k, v, w_log, u, chunk: int = 16, n_shards: int = 16):
    """Sequence-parallel chunked WKV (§Perf iteration 2 for the rwkv cells).

    With the sequence dim sharded, a single chunk scan makes every device
    execute every iteration behind a select (full-buffer write per step).
    Instead: (1) each seq shard runs the chunked recurrence from zero state
    *in parallel*; (2) an associative scan over shards composes
    (decay-product, local-state) pairs — the recurrence is linear in the
    state so shard composition is associative; (3) one correction einsum
    adds the incoming state's contribution.  The scanned dim is now
    shard-local, so the ys write is a true in-place slice update.
    """
    B, S, H, hs = r.shape
    G = n_shards
    Sg = S // G
    rs = r.reshape(B, G, Sg, H, hs)
    ks = k.reshape(B, G, Sg, H, hs)
    vs = v.reshape(B, G, Sg, H, hs)
    ws = w_log.astype(jnp.float32).reshape(B, G, Sg, H, hs)
    rs = maybe_constrain(rs, ("batch", "seq_q", None, "rwkv_heads", "head_dim"))

    def local(rg, kg, vg, wg):                    # (B,Sg,H,hs) each
        return wkv_chunked(rg, kg, vg, wg, u, chunk)

    o_loc, T = jax.vmap(local, in_axes=1, out_axes=(1, 1))(rs, ks, vs, ws)

    lp = jnp.cumsum(ws, axis=2)                   # within-shard inclusive
    lp_prev = lp - ws
    D = jnp.exp(lp[:, :, -1])                     # (B,G,H,hs) shard decay

    def combine(c1, c2):
        d1, t1 = c1
        d2, t2 = c2
        return d1 * d2, d2[..., None] * t1 + t2   # decay acts on the k dim

    Dx, Tx = jax.lax.associative_scan(combine, (D, T), axis=1)
    s_in = jnp.concatenate([jnp.zeros_like(Tx[:, :1]), Tx[:, :-1]], axis=1)
    corr = jnp.einsum("bgshk,bghkv->bgshv",
                      rs * jnp.exp(lp_prev).astype(rs.dtype), s_in,
                      preferred_element_type=jnp.float32)
    o = (o_loc + corr).reshape(B, S, H, hs)
    return o, Tx[:, -1]


def _group_norm(p, o):
    """Per-head LayerNorm of (B,S,H,hs)."""
    mu = o.mean(axis=-1, keepdims=True)
    var = o.var(axis=-1, keepdims=True)
    y = (o - mu) * jax.lax.rsqrt(var + 64e-5)
    return y * p["ln_scale"].astype(y.dtype) + p["ln_bias"].astype(y.dtype)


def _wkv_kernel(r, k, v, w_log, u):
    """The Pallas WKV kernel (``kernels/rwkv6_kernel.py``) in this module's
    (B,S,H,hs) layout; the kernel's is (B,H,S,hs).  Returns o only."""
    from ..kernels import ops

    def tr(t):
        return t.transpose(0, 2, 1, 3)
    return tr(ops.rwkv6(tr(r), tr(k), tr(v), tr(w_log), u, use_pallas=True))


def apply_timemix(p, x, *, n_heads, head_size, use_pallas=False,
                  return_state=False):
    """Full-sequence time-mix. x: (B,S,D) -> (out (B,S,D), state): with
    ``return_state`` the decode state after the last position (``tm_x``,
    ``wkv``), else None.  The Pallas kernel runs under ``use_pallas`` when
    no state is asked for."""
    B, S, D = x.shape
    xx = jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :S]          # prev token
    mixed = _ddlerp(p, x, xx)                                 # (B,S,5,D)
    x_w, x_k, x_v, x_r, x_g = [mixed[:, :, i] for i in range(FIVE)]
    r = jnp.einsum("bsd,dhk->bshk", x_r, p["wr"])
    k = jnp.einsum("bsd,dhk->bshk", x_k, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x_v, p["wv"])
    g = jax.nn.silu(jnp.einsum("bsd,dhk->bshk", x_g, p["wg"]))
    w_log = -jnp.exp(p["w0"].astype(jnp.float32)
                     + jnp.einsum("bsd,dl->bsl", x_w, p["wA"]).astype(jnp.float32)
                     @ p["wB"].astype(jnp.float32))
    w_log = w_log.reshape(B, S, n_heads, head_size)
    r = maybe_constrain(r, ("batch", None, "rwkv_heads", "head_dim"))
    # chunked by default at seq >= 64 (~16x less state HBM traffic);
    # sequence-parallel chunked at long seq (in-place ys writes under
    # sequence sharding); exact sequential scan for short sequences
    if use_pallas and not return_state:
        o, final = _wkv_kernel(r, k, v, w_log, p["u"]), None
    elif S >= 4096 and S % 256 == 0:
        o, final = wkv_seq_parallel(r, k, v, w_log, p["u"])
    elif S >= 64:
        o, final = wkv_chunked(r, k, v, w_log, p["u"])
    else:
        o, final = _wkv_scan(r, k, v, w_log, p["u"])
    o = _group_norm(p, o.astype(jnp.float32)).astype(x.dtype) * g
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return out, ({"tm_x": x[:, -1], "wkv": final} if return_state else None)


def apply_channelmix(p, x):
    B, S, D = x.shape
    xx = jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :S]
    x_k = x + (xx - x) * p["mu_k"].astype(x.dtype)
    x_r = x + (xx - x) * p["mu_r"].astype(x.dtype)
    k = jnp.square(jax.nn.relu(jnp.einsum("bsd,df->bsf", x_k, p["wk"])))
    kv = jnp.einsum("bsf,fd->bsd", k, p["wv"])
    return jax.nn.sigmoid(jnp.einsum("bsd,de->bse", x_r, p["wr"])) * kv


# ----------------------------------------------------------------- decode

def rwkv_state_shapes(batch, d, n_heads, head_size, dtype):
    return {
        "tm_x": jax.ShapeDtypeStruct((batch, d), dtype),
        "cm_x": jax.ShapeDtypeStruct((batch, d), dtype),
        "wkv": jax.ShapeDtypeStruct((batch, n_heads, head_size, head_size),
                                    jnp.float32),
    }


RWKV_STATE_AXES = {"tm_x": ("batch", "embed"), "cm_x": ("batch", "embed"),
                   "wkv": ("batch", "rwkv_heads", "head_dim", None)}


def decode_timemix(p, state, x, *, n_heads, head_size):
    """x: (B,1,D) -> (out, new tm_x, new wkv state)."""
    B, _, D = x.shape
    xx = state["tm_x"][:, None]
    mixed = _ddlerp(p, x, xx)
    x_w, x_k, x_v, x_r, x_g = [mixed[:, :, i] for i in range(FIVE)]
    r = jnp.einsum("bsd,dhk->bshk", x_r, p["wr"])[:, 0].astype(jnp.float32)
    k = jnp.einsum("bsd,dhk->bshk", x_k, p["wk"])[:, 0].astype(jnp.float32)
    v = jnp.einsum("bsd,dhk->bshk", x_v, p["wv"])[:, 0].astype(jnp.float32)
    g = jax.nn.silu(jnp.einsum("bsd,dhk->bshk", x_g, p["wg"]))[:, 0]
    w_log = -jnp.exp(p["w0"].astype(jnp.float32)
                     + jnp.einsum("bsd,dl->bsl", x_w, p["wA"]).astype(jnp.float32)
                     @ p["wB"].astype(jnp.float32))[:, 0]
    w = jnp.exp(w_log.reshape(B, n_heads, head_size))
    uf = p["u"].astype(jnp.float32)
    kv = k[..., :, None] * v[..., None, :]
    o = jnp.einsum("bhk,bhkv->bhv", r, state["wkv"] + uf[None, :, :, None] * kv)
    new_wkv = w[..., :, None] * state["wkv"] + kv
    o = _group_norm(p, o[:, None].astype(jnp.float32))[:, 0].astype(x.dtype) * g
    out = jnp.einsum("bhk,hkd->bd", o, p["wo"])[:, None]
    return out, x[:, 0], new_wkv


def decode_channelmix(p, state, x):
    xx = state["cm_x"][:, None]
    x_k = x + (xx - x) * p["mu_k"].astype(x.dtype)
    x_r = x + (xx - x) * p["mu_r"].astype(x.dtype)
    k = jnp.square(jax.nn.relu(jnp.einsum("bsd,df->bsf", x_k, p["wk"])))
    kv = jnp.einsum("bsf,fd->bsd", k, p["wv"])
    out = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", x_r, p["wr"])) * kv
    return out, x[:, 0]
