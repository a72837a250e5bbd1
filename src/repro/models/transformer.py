"""Composable decoder stack over heterogeneous block patterns.

Supports all 10 assigned architectures through ``ModelConfig``:
dense/MoE GQA attention blocks, RG-LRU recurrent blocks, RWKV6 blocks,
VLM patch-prefix and multi-codebook audio frontends.  Layers are grouped
into repeating *pattern units* (e.g. ("rec","rec","attn") for
recurrentgemma); in training and prefill, units are either scanned
(stacked params, production default) or unrolled (D3 search factor).  The
decode state is one tree per layer, and decode always unrolls the layers,
so that each layer's cache is a buffer of its own, written in place.

Named scopes mark the layers in the compiled programs' metadata, and so in
the device trace: ``embed``, ``layers`` (the unit loop or scan, and the
tail), ``attn`` and ``mlp`` (each sublayer of an attention block with its
norm and residual), ``attn/kv_cache`` (the cache written), ``final_norm``,
``unembed`` and ``loss``.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from . import attention as attn
from . import moe as moe_mod
from . import rglru as rg
from . import rwkv6 as rwkv
from .layers import (apply_glu_mlp, apply_norm, apply_plain_mlp, embed_lookup,
                     glu_mlp_specs, norm_specs, plain_mlp_specs)
from .module import ParamSpec, map_specs, stack_layer_specs
from ..configs.base import ModelConfig, RunPolicy, ShapeSpec
from ..launch.sharding import maybe_constrain

# ----------------------------------------------------------------- spec build

def block_specs(cfg: ModelConfig, bt: str):
    if bt == "attn":
        if cfg.n_experts:
            mlp = moe_mod.moe_specs(cfg.d_model, cfg.d_ff, cfg.n_experts)
        elif cfg.act == "gelu" and cfg.norm == "layernorm":
            mlp = plain_mlp_specs(cfg.d_model, cfg.d_ff)   # musicgen-style
        else:
            mlp = glu_mlp_specs(cfg.d_model, cfg.d_ff)
        return {"ln1": norm_specs(cfg.d_model, cfg.norm),
                "attn": attn.attn_specs(cfg.d_model, cfg.n_heads,
                                        cfg.n_kv_heads, cfg.d_head, cfg.qkv_bias),
                "ln2": norm_specs(cfg.d_model, cfg.norm),
                "mlp": mlp}
    if bt == "rec":
        return {"ln1": norm_specs(cfg.d_model, cfg.norm),
                "rec": rg.rglru_specs(cfg.d_model, cfg.rec_width, cfg.n_heads),
                "ln2": norm_specs(cfg.d_model, cfg.norm),
                "mlp": glu_mlp_specs(cfg.d_model, cfg.d_ff)}
    if bt == "rwkv":
        return {"ln1": norm_specs(cfg.d_model, cfg.norm),
                "tm": rwkv.timemix_specs(cfg.d_model, cfg.n_heads, cfg.head_size),
                "ln2": norm_specs(cfg.d_model, cfg.norm),
                "cm": rwkv.channelmix_specs(cfg.d_model, cfg.d_ff)}
    raise ValueError(bt)


def n_units_tail(cfg: ModelConfig):
    plen = len(cfg.block_pattern)
    return cfg.n_layers // plen, cfg.n_layers % plen


def build_specs(cfg: ModelConfig):
    n_units, tail = n_units_tail(cfg)
    unit = {f"b{i}": block_specs(cfg, bt) for i, bt in enumerate(cfg.block_pattern)}
    specs: dict[str, Any] = {
        "embed": _embed_specs(cfg),
        "units": map_specs(lambda s: stack_layer_specs(s, n_units), unit),
        "final_norm": norm_specs(cfg.d_model, cfg.norm),
    }
    if tail:
        specs["tail"] = {f"t{i}": block_specs(cfg, cfg.block_pattern[i])
                         for i in range(tail)}
    if not cfg.tie_embeddings:
        specs["unembed"] = _unembed_specs(cfg)
    if cfg.frontend == "vit":
        specs["projector"] = {
            "ln": norm_specs(cfg.d_frontend, cfg.norm),
            "w1": ParamSpec((cfg.d_frontend, cfg.d_model), (None, "embed")),
            "w2": ParamSpec((cfg.d_model, cfg.d_model), ("embed", None)),
        }
    return specs


def _embed_specs(cfg):
    if cfg.frontend == "encodec":
        return {"table": ParamSpec((cfg.n_codebooks, cfg.vocab_size, cfg.d_model),
                                   (None, "vocab", "embed"), "embed")}
    return {"table": ParamSpec((cfg.vocab_size, cfg.d_model),
                               ("vocab", "embed"), "embed")}


def _unembed_specs(cfg):
    if cfg.frontend == "encodec":
        return {"table": ParamSpec((cfg.n_codebooks, cfg.vocab_size, cfg.d_model),
                                   (None, "vocab", "embed"), "embed")}
    return {"table": ParamSpec((cfg.vocab_size, cfg.d_model),
                               ("vocab", "embed"), "embed")}


# ------------------------------------------------------------------ embedding

@jax.named_scope("embed")
def embed_tokens(params, cfg: ModelConfig, batch, compute_dtype):
    """Returns (x (B,S,D), positions (B,S), label_mask_prefix)."""
    table = params["embed"]["table"]
    if cfg.frontend == "encodec":
        toks = batch["tokens"]                       # (B,S,K)
        x = sum(jnp.take(table[k], toks[..., k], axis=0)
                for k in range(cfg.n_codebooks))
    else:
        x = embed_lookup(params["embed"], batch["tokens"])
    x = x.astype(compute_dtype)
    if cfg.frontend == "vit" and "patch_embeds" in batch:
        pe = batch["patch_embeds"].astype(compute_dtype)      # (B,P,df)
        pr = params["projector"]
        h = apply_norm(pr["ln"], pe, cfg.norm)
        h = jax.nn.gelu(jnp.einsum("bpd,de->bpe", h, pr["w1"].astype(compute_dtype)))
        h = jnp.einsum("bpd,de->bpe", h, pr["w2"].astype(compute_dtype))
        x = jnp.concatenate([h, x], axis=1)
    if cfg.embed_scale:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    return x, positions


@jax.named_scope("unembed")
def unembed_logits(params, cfg: ModelConfig, x):
    table = (params["embed"] if cfg.tie_embeddings else params["unembed"])["table"]
    if cfg.frontend == "encodec":
        logits = jnp.einsum("...d,kvd->...kv", x.astype(jnp.float32),
                            table.astype(jnp.float32))
    else:
        logits = jnp.einsum("...d,vd->...v", x.astype(jnp.float32),
                            table.astype(jnp.float32))
    if cfg.logit_softcap:
        c = cfg.logit_softcap
        logits = jnp.tanh(logits / c) * c
    return logits


# ------------------------------------------------------------ full-seq blocks

def _resolve_attn_impl(cfg, policy, S):
    if policy.use_pallas:
        return "pallas"
    if policy.attn_impl != "auto":
        return policy.attn_impl
    if cfg.window is not None and S > 2 * cfg.window:
        return "local"
    if S >= 2048:
        return "blocked"     # flash-attention algebra: matches the TPU kernel
    return "plain"


def apply_block_full(bt, p, x, positions, cfg: ModelConfig, policy: RunPolicy,
                     cache_len: int | None = None):
    """Returns (x, aux (2,) f32, state-or-None)."""
    aux = jnp.zeros((2,), jnp.float32)
    state = None
    S = x.shape[1]
    if bt == "attn":
        with jax.named_scope("attn"):
            h = apply_norm(p["ln1"], x, cfg.norm)
            impl = _resolve_attn_impl(cfg, policy, S)
            kw = dict(n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                      d_head=cfg.d_head, rope_theta=cfg.rope_theta,
                      window=cfg.window, use_rope=cfg.use_rope)
            if cache_len is None:
                a = attn.full_attention(p["attn"], h, positions, impl=impl,
                                        **kw)
            else:
                q, k, v = attn.qkv_proj(p["attn"], h, cfg.n_heads,
                                        cfg.n_kv_heads, cfg.d_head, positions,
                                        cfg.rope_theta, cfg.use_rope)
                if impl == "pallas":
                    o = attn.pallas_attention(q, k, v, cfg.window)
                elif impl == "local":
                    o = attn.local_chunk_attention(q, k, v, positions,
                                                   positions, cfg.window)
                elif impl == "blocked":
                    o = attn.blocked_attention(q, k, v, positions, positions,
                                               cfg.window)
                else:
                    o = attn.plain_attention(q, k, v, positions, positions,
                                             cfg.window)
                a = attn.out_proj(p["attn"], o)
                state = _cache_from_kv(k, v, positions, cache_len, cfg)
            x = x + a
        with jax.named_scope("mlp"):
            h2 = apply_norm(p["ln2"], x, cfg.norm)
            if cfg.n_experts:
                m, moe_aux = moe_mod.apply_moe(
                    p["mlp"], h2, top_k=cfg.top_k, act=cfg.act,
                    capacity_factor=policy.capacity_factor)
                aux = jnp.stack([moe_aux["lb_loss"], moe_aux["dropped_frac"]])
            elif "wi" in p["mlp"]:
                m = apply_plain_mlp(p["mlp"], h2, cfg.act)
            else:
                m = apply_glu_mlp(p["mlp"], h2, cfg.act)
            x = x + m
    elif bt == "rec":
        h = apply_norm(p["ln1"], x, cfg.norm)
        if cache_len is None:
            r = rg.apply_rglru(p["rec"], h, n_blocks=cfg.n_heads,
                               use_pallas=policy.use_pallas)
        else:
            r, state = _rglru_with_state(p["rec"], h, cfg)
        x = x + r
        h2 = apply_norm(p["ln2"], x, cfg.norm)
        x = x + apply_glu_mlp(p["mlp"], h2, cfg.act)
    elif bt == "rwkv":
        h = apply_norm(p["ln1"], x, cfg.norm)
        if cache_len is None:
            wkv_fn = None
            if policy.use_pallas:
                from ..kernels import ops

                def wkv_fn(r, k, v, w_log, u):
                    tr = lambda t: t.transpose(0, 2, 1, 3)
                    o = ops.rwkv6(tr(r), tr(k), tr(v), tr(w_log), u,
                                  use_pallas=True)
                    return tr(o)
            t = rwkv.apply_timemix(p["tm"], h, n_heads=cfg.n_heads,
                                   head_size=cfg.head_size, wkv_fn=wkv_fn)
        else:
            t, state = _rwkv_with_state(p, h, x, cfg)
        x = x + t
        h2 = apply_norm(p["ln2"], x, cfg.norm)
        x = x + rwkv.apply_channelmix(p["cm"], h2)
        if cache_len is not None:
            state["cm_x"] = h2[:, -1]
    else:
        raise ValueError(bt)
    x = maybe_constrain(x, ("batch", "seq_q", "act_embed"))
    return x, aux, state


@jax.named_scope("kv_cache")
def _cache_from_kv(k, v, positions, cache_len, cfg):
    B, S = k.shape[:2]
    if cfg.window is not None and cache_len < S:
        keep = cache_len
        kk, vv, pos = k[:, -keep:], v[:, -keep:], positions[:, -keep:]
        slot = pos % cache_len
        bidx = jnp.arange(B)[:, None]
        ck = jnp.zeros((B, cache_len) + k.shape[2:], k.dtype).at[bidx, slot].set(kk)
        cv = jnp.zeros_like(ck).at[bidx, slot].set(vv)
        cp = jnp.full((B, cache_len), -1, jnp.int32).at[bidx, slot].set(pos)
        return {"k": ck, "v": cv, "pos": cp}
    pad = cache_len - S
    return {"k": jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))),
            "v": jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))),
            "pos": jnp.pad(positions, ((0, 0), (0, pad)), constant_values=-1)}


def _rglru_with_state(p, h, cfg):
    """RG-LRU full pass that also returns the decode state."""
    xb = jnp.einsum("bsd,dw->bsw", h, p["wx"])
    xb_conv = rg._conv_full(p, xb)
    r, i = rg._gates(p, xb_conv, cfg.n_heads)
    log_a = rg._log_a(p, r)
    a = jnp.exp(log_a)
    gated = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) \
        * (i * xb_conv.astype(jnp.float32))

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    _, hs = jax.lax.associative_scan(combine, (a, gated), axis=1)
    y = jax.nn.gelu(jnp.einsum("bsd,dw->bsw", h, p["wy"]))
    out = jnp.einsum("bsw,wd->bsd", hs.astype(h.dtype) * y, p["wo"])
    K = rg.CONV_K  # conv state = last K-1 raw (pre-conv) inputs
    hist = jnp.pad(xb, ((0, 0), (K - 1, 0), (0, 0)))[:, -(K - 1):, :]
    state = {"h": hs[:, -1], "conv": hist}
    return out, state


def _rwkv_with_state(p, h, x_res, cfg):
    B, S, D = h.shape
    xx = jnp.pad(h, ((0, 0), (1, 0), (0, 0)))[:, :S]
    mixed = rwkv._ddlerp(p["tm"], h, xx)
    x_w, x_k, x_v, x_r, x_g = [mixed[:, :, i] for i in range(rwkv.FIVE)]
    r = jnp.einsum("bsd,dhk->bshk", x_r, p["tm"]["wr"])
    k = jnp.einsum("bsd,dhk->bshk", x_k, p["tm"]["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x_v, p["tm"]["wv"])
    g = jax.nn.silu(jnp.einsum("bsd,dhk->bshk", x_g, p["tm"]["wg"]))
    w_log = -jnp.exp(p["tm"]["w0"].astype(jnp.float32)
                     + jnp.einsum("bsd,dl->bsl", x_w, p["tm"]["wA"]).astype(jnp.float32)
                     @ p["tm"]["wB"].astype(jnp.float32))
    w_log = w_log.reshape(B, S, cfg.n_heads, cfg.head_size)
    if S >= 4096 and S % 256 == 0:
        o, final = rwkv.wkv_seq_parallel(r, k, v, w_log, p["tm"]["u"])
    elif S >= 64:
        o, final = rwkv.wkv_chunked(r, k, v, w_log, p["tm"]["u"])
    else:
        o, final = _wkv_scan_with_state(r, k, v, w_log, p["tm"]["u"])
    o = rwkv._group_norm(p["tm"], o.astype(jnp.float32)).astype(h.dtype) * g
    out = jnp.einsum("bshk,hkd->bsd", o, p["tm"]["wo"])
    state = {"tm_x": h[:, -1], "cm_x": jnp.zeros_like(h[:, -1]), "wkv": final}
    return out, state


def _wkv_scan_with_state(r, k, v, w_log, u):
    B, S, H, hs = r.shape
    rf = r.astype(jnp.float32).swapaxes(0, 1)
    kf = k.astype(jnp.float32).swapaxes(0, 1)
    vf = v.astype(jnp.float32).swapaxes(0, 1)
    wf = jnp.exp(w_log.astype(jnp.float32)).swapaxes(0, 1)
    uf = u.astype(jnp.float32)

    def step(state, inp):
        rt, kt, vt, wt = inp
        kv = kt[..., :, None] * vt[..., None, :]
        o = jnp.einsum("bhk,bhkv->bhv", rt, state + uf[None, :, :, None] * kv)
        return wt[..., :, None] * state + kv, o

    s0 = jnp.zeros((B, H, hs, hs), jnp.float32)
    final, o = jax.lax.scan(step, s0, (rf, kf, vf, wf))
    return o.swapaxes(0, 1), final


# -------------------------------------------------------------- decode blocks

def apply_block_decode(bt, p, state, x, position, cfg: ModelConfig,
                       policy: RunPolicy | None = None):
    cf = policy.capacity_factor if policy is not None else 1.25
    if bt == "attn":
        with jax.named_scope("attn"):
            h = apply_norm(p["ln1"], x, cfg.norm)
            o, new_cache = attn.decode_attention(
                p["attn"], state, h, position, n_heads=cfg.n_heads,
                n_kv=cfg.n_kv_heads, d_head=cfg.d_head,
                rope_theta=cfg.rope_theta, window=cfg.window,
                use_rope=cfg.use_rope)
            x = x + attn.out_proj(p["attn"], o)
        with jax.named_scope("mlp"):
            h2 = apply_norm(p["ln2"], x, cfg.norm)
            if cfg.n_experts:
                m, _ = moe_mod.apply_moe(p["mlp"], h2, top_k=cfg.top_k,
                                         act=cfg.act, capacity_factor=cf)
            elif "wi" in p["mlp"]:
                m = apply_plain_mlp(p["mlp"], h2, cfg.act)
            else:
                m = apply_glu_mlp(p["mlp"], h2, cfg.act)
            return x + m, new_cache
    if bt == "rec":
        h = apply_norm(p["ln1"], x, cfg.norm)
        r, new_state = rg.decode_rglru(p["rec"], state, h, n_blocks=cfg.n_heads)
        x = x + r
        h2 = apply_norm(p["ln2"], x, cfg.norm)
        return x + apply_glu_mlp(p["mlp"], h2, cfg.act), new_state
    if bt == "rwkv":
        h = apply_norm(p["ln1"], x, cfg.norm)
        t, tm_x, wkv_s = rwkv.decode_timemix(p["tm"], state, h,
                                             n_heads=cfg.n_heads,
                                             head_size=cfg.head_size)
        x = x + t
        h2 = apply_norm(p["ln2"], x, cfg.norm)
        c, cm_x = rwkv.decode_channelmix(p["cm"], state, h2)
        return x + c, {"tm_x": tm_x, "cm_x": cm_x, "wkv": wkv_s}
    raise ValueError(bt)


# ------------------------------------------------------------- state builders

def block_state_shapes(cfg: ModelConfig, bt: str, batch: int, cache_len: int,
                       dtype):
    if bt == "attn":
        clen = min(cache_len, cfg.window) if cfg.window else cache_len
        return attn.cache_shapes(batch, clen, cfg.n_kv_heads, cfg.d_head, dtype)
    if bt == "rec":
        return rg.rglru_state_shapes(batch, cfg.rec_width, dtype)
    if bt == "rwkv":
        return rwkv.rwkv_state_shapes(batch, cfg.d_model, cfg.n_heads,
                                      cfg.head_size, dtype)
    raise ValueError(bt)


def block_state_axes(bt: str):
    return {"attn": attn.CACHE_AXES, "rec": rg.RGLRU_STATE_AXES,
            "rwkv": rwkv.RWKV_STATE_AXES}[bt]


def layer_types(cfg: ModelConfig):
    """The block type of every layer, in layer order."""
    plen = len(cfg.block_pattern)
    return [cfg.block_pattern[i % plen] for i in range(cfg.n_layers)]


def layer_params(params, cfg: ModelConfig):
    """Every layer's parameters, in layer order: the stacked units' slices,
    then the tail."""
    n_units, tail = n_units_tail(cfg)
    plen = len(cfg.block_pattern)
    out = [jax.tree.map(lambda a: a[u], params["units"][f"b{i}"])
           for u in range(n_units) for i in range(plen)]
    return out + [params["tail"][f"t{i}"] for i in range(tail)]


def model_state_shapes(cfg: ModelConfig, batch: int, cache_len: int, dtype):
    """The decode state: one tree per layer, in layer order, each leaf
    ``(batch, ...)``, so that each layer's cache is a buffer of its own."""
    return [block_state_shapes(cfg, bt, batch, cache_len, dtype)
            for bt in layer_types(cfg)]


def model_state_axes(cfg: ModelConfig):
    return [dict(block_state_axes(bt)) for bt in layer_types(cfg)]


# --------------------------------------------------------------- full forward

def _remat_wrap(fn, policy: RunPolicy):
    if policy.remat == "none":
        return fn
    pol = {"dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
           "full": jax.checkpoint_policies.nothing_saveable}[policy.remat]
    return jax.checkpoint(fn, policy=pol)


def forward(params, batch, cfg: ModelConfig, policy: RunPolicy,
            return_cache: bool = False, cache_len: int | None = None):
    """Full-sequence forward.

    Returns (logits, aux) for training (full-seq logits), or
    (last_logits, aux, state) when return_cache (prefill).
    """
    compute_dtype = jnp.bfloat16 if policy.dtype == "bf16" else jnp.float32
    cparams = jax.tree.map(lambda a: a.astype(compute_dtype)
                           if a.dtype == jnp.float32 else a, params)
    x, positions = embed_tokens(cparams, cfg, batch, compute_dtype)
    x = maybe_constrain(x, ("batch", "seq_q", "act_embed"))
    pattern = cfg.block_pattern
    n_units, tail = n_units_tail(cfg)
    cl = cache_len if return_cache else None

    def unit_fn(x, unit_params, positions):
        aux = jnp.zeros((2,), jnp.float32)
        states = []
        for i, bt in enumerate(pattern):
            x, a, st = apply_block_full(bt, unit_params[f"b{i}"], x, positions,
                                        cfg, policy, cache_len=cl)
            aux = aux + a
            if cl is not None:
                states.append(st)
        return x, aux, states

    unit_fn_r = _remat_wrap(unit_fn, policy)

    layer_states = []
    with jax.named_scope("layers"):
        if policy.scan_layers and n_units > 1:
            def scan_body(carry, unit_params):
                x, acc = carry
                x, aux, states = unit_fn_r(x, unit_params, positions)
                return (x, acc + aux), states
            (x, aux), states = jax.lax.scan(
                scan_body, (x, jnp.zeros((2,), jnp.float32)), cparams["units"])
            # the decode state's layout: one tree per layer
            layer_states = [jax.tree.map(lambda a: a[u], st)
                            for u in range(n_units) for st in states]
        else:
            aux = jnp.zeros((2,), jnp.float32)
            for u in range(n_units):
                up = jax.tree.map(lambda a: a[u], cparams["units"])
                x, a, states = unit_fn_r(x, up, positions)
                aux = aux + a
                layer_states += states

        for i in range(tail):
            bt = pattern[i]
            x, a, st = apply_block_full(bt, cparams["tail"][f"t{i}"], x,
                                        positions, cfg, policy, cache_len=cl)
            aux = aux + a
            if cl is not None:
                layer_states.append(st)

    with jax.named_scope("final_norm"):
        x = apply_norm(cparams["final_norm"], x, cfg.norm)
    if return_cache:
        last = x[:, -1]
        logits = unembed_logits(cparams, cfg, last)
        return logits, aux, layer_states
    logits = unembed_logits(cparams, cfg, x)
    return logits, aux


def decode_step(params, state, batch, cfg: ModelConfig, policy: RunPolicy):
    """One-token decode.  batch: {"tokens": (B,1[,K]), "position": (B,)}.

    Returns (logits (B,V) or (B,K,V), new_state).
    """
    compute_dtype = jnp.bfloat16 if policy.dtype == "bf16" else jnp.float32
    cparams = jax.tree.map(lambda a: a.astype(compute_dtype)
                           if a.dtype == jnp.float32 else a, params)
    x, _ = embed_tokens(cparams, cfg, batch, compute_dtype)
    position = batch["position"]
    new_state = []
    # the layers unrolled, whatever policy.scan_layers says: a scan would
    # slice each layer's cache out of a stack and write it back, where each
    # layer here reads and writes its own buffer in place
    with jax.named_scope("layers"):
        for bt, p, st in zip(layer_types(cfg), layer_params(cparams, cfg),
                             state, strict=True):
            x, st = apply_block_decode(bt, p, st, x, position, cfg, policy)
            new_state.append(st)

    with jax.named_scope("final_norm"):
        x = apply_norm(cparams["final_norm"], x, cfg.norm)
    logits = unembed_logits(cparams, cfg, x[:, 0])
    return logits, new_state


# ----------------------------------------------------------------------- loss

@jax.named_scope("loss")
def lm_loss(logits, labels):
    """Cross-entropy with mask (labels < 0 ignored). logits f32."""
    V = logits.shape[-1]
    mask = (labels >= 0)
    labels_c = jnp.clip(labels, 0, V - 1)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels_c[..., None], axis=-1)[..., 0]
    n = jnp.maximum(mask.sum(), 1)
    return -(ll * mask).sum() / n
