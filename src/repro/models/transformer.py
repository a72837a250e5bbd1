"""Composable decoder stack over heterogeneous block patterns.

Supports all 10 assigned architectures through ``ModelConfig``:
dense/MoE GQA attention blocks, RG-LRU recurrent blocks, RWKV6 blocks,
VLM patch-prefix and multi-codebook audio frontends.  Layers are grouped
into repeating *pattern units* (e.g. ("rec","rec","attn") for
recurrentgemma); in training and prefill, units are either scanned
(stacked params, production default) or unrolled (D3 search factor).
Training and prefill run the same full-sequence block; prefill only asks
it for the decode state as well.  The decode state is one tree per layer,
and decode always unrolls the layers, so that each layer's cache is a
buffer of its own, written in place.

Named scopes mark the layers in the compiled programs' metadata, and so in
the device trace: ``embed``, ``layers`` (the unit loop or scan, and the
tail), ``attn`` and ``mlp`` (each sublayer of an attention block with its
norm and residual), ``attn/kv_cache`` (the cache written), ``final_norm``,
``unembed`` and ``loss``.
"""
from __future__ import annotations

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
from ..configs.base import ModelConfig, RunPolicy
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
        "embed": _table_specs(cfg),
        "units": map_specs(lambda s: stack_layer_specs(s, n_units), unit),
        "final_norm": norm_specs(cfg.d_model, cfg.norm),
    }
    if tail:
        specs["tail"] = {f"t{i}": block_specs(cfg, cfg.block_pattern[i])
                         for i in range(tail)}
    if not cfg.tie_embeddings:
        specs["unembed"] = _table_specs(cfg)
    if cfg.frontend == "vit":
        specs["projector"] = {
            "ln": norm_specs(cfg.d_frontend, cfg.norm),
            "w1": ParamSpec((cfg.d_frontend, cfg.d_model), (None, "embed")),
            "w2": ParamSpec((cfg.d_model, cfg.d_model), ("embed", None)),
        }
    return specs


def _table_specs(cfg):
    """The embedding table, and an untied unembedding's."""
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


# --------------------------------------------------------------------- blocks

def _ffn(p, h, cfg: ModelConfig, policy: RunPolicy):
    """An attention block's feed-forward: routed experts, a plain MLP or a
    GLU.  Returns (out, the router's (2,) f32 statistics or None)."""
    if cfg.n_experts:
        m, a = moe_mod.apply_moe(p, h, top_k=cfg.top_k, act=cfg.act,
                                 capacity_factor=policy.capacity_factor)
        return m, jnp.stack([a["lb_loss"], a["dropped_frac"]])
    if "wi" in p:
        return apply_plain_mlp(p, h, cfg.act), None
    return apply_glu_mlp(p, h, cfg.act), None


def apply_block_full(bt, p, x, positions, cfg: ModelConfig, policy: RunPolicy,
                     cache_len: int | None = None):
    """One block over the whole sequence: training, or prefill when
    ``cache_len`` is given.  Returns (x, aux (2,) f32, the block's decode
    state after the last position, or None without ``cache_len``)."""
    aux = jnp.zeros((2,), jnp.float32)
    want_state = cache_len is not None
    if bt == "attn":
        with jax.named_scope("attn"):
            a, state = attn.self_attention(
                p["attn"], apply_norm(p["ln1"], x, cfg.norm), positions, cfg,
                policy, cache_len)
            x = x + a
        with jax.named_scope("mlp"):
            m, router = _ffn(p["mlp"], apply_norm(p["ln2"], x, cfg.norm), cfg,
                             policy)
            x = x + m
        if router is not None:
            aux = router
    elif bt == "rec":
        r, state = rg.apply_rglru(p["rec"], apply_norm(p["ln1"], x, cfg.norm),
                                  n_blocks=cfg.n_heads,
                                  use_pallas=policy.use_pallas,
                                  return_state=want_state)
        x = x + r
        x = x + apply_glu_mlp(p["mlp"], apply_norm(p["ln2"], x, cfg.norm),
                              cfg.act)
    elif bt == "rwkv":
        t, state = rwkv.apply_timemix(p["tm"], apply_norm(p["ln1"], x, cfg.norm),
                                      n_heads=cfg.n_heads,
                                      head_size=cfg.head_size,
                                      use_pallas=policy.use_pallas,
                                      return_state=want_state)
        x = x + t
        h2 = apply_norm(p["ln2"], x, cfg.norm)
        x = x + rwkv.apply_channelmix(p["cm"], h2)
        if want_state:
            state = {**state, "cm_x": h2[:, -1]}   # channel-mix's last input
    else:
        raise ValueError(bt)
    x = maybe_constrain(x, ("batch", "seq_q", "act_embed"))
    return x, aux, state


def apply_block_decode(bt, p, state, x, position, cfg: ModelConfig,
                       policy: RunPolicy):
    """One block for one token per lane.  Returns (x, the new state)."""
    if bt == "attn":
        with jax.named_scope("attn"):
            a, state = attn.decode_attention(
                p["attn"], state, apply_norm(p["ln1"], x, cfg.norm), position,
                cfg)
            x = x + a
        with jax.named_scope("mlp"):
            m, _ = _ffn(p["mlp"], apply_norm(p["ln2"], x, cfg.norm), cfg,
                        policy)
            return x + m, state
    if bt == "rec":
        r, state = rg.decode_rglru(p["rec"], state,
                                   apply_norm(p["ln1"], x, cfg.norm),
                                   n_blocks=cfg.n_heads)
        x = x + r
        return x + apply_glu_mlp(p["mlp"], apply_norm(p["ln2"], x, cfg.norm),
                                 cfg.act), state
    if bt == "rwkv":
        t, tm_x, wkv = rwkv.decode_timemix(p["tm"], state,
                                           apply_norm(p["ln1"], x, cfg.norm),
                                           n_heads=cfg.n_heads,
                                           head_size=cfg.head_size)
        x = x + t
        c, cm_x = rwkv.decode_channelmix(p["cm"], state,
                                         apply_norm(p["ln2"], x, cfg.norm))
        return x + c, {"tm_x": tm_x, "cm_x": cm_x, "wkv": wkv}
    raise ValueError(bt)


# ------------------------------------------------------------- state builders

def block_state_shapes(cfg: ModelConfig, bt: str, batch: int, cache_len: int,
                       dtype):
    if bt == "attn":
        return attn.cache_shapes(batch, cache_len, cfg, dtype)
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
            cache_len: int | None = None):
    """Full-sequence forward.

    Returns (logits, aux) for training (full-seq logits), or, with
    ``cache_len`` (prefill), (last_logits, aux, state): the decode state,
    one tree per layer as ``model_state_shapes`` gives it.
    """
    compute_dtype = jnp.bfloat16 if policy.dtype == "bf16" else jnp.float32
    cparams = jax.tree.map(lambda a: a.astype(compute_dtype)
                           if a.dtype == jnp.float32 else a, params)
    x, positions = embed_tokens(cparams, cfg, batch, compute_dtype)
    x = maybe_constrain(x, ("batch", "seq_q", "act_embed"))
    pattern = cfg.block_pattern
    n_units, tail = n_units_tail(cfg)

    def unit_fn(x, unit_params, positions):
        aux = jnp.zeros((2,), jnp.float32)
        states = []
        for i, bt in enumerate(pattern):
            x, a, st = apply_block_full(bt, unit_params[f"b{i}"], x, positions,
                                        cfg, policy, cache_len)
            aux = aux + a
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
            x, a, st = apply_block_full(pattern[i], cparams["tail"][f"t{i}"], x,
                                        positions, cfg, policy, cache_len)
            aux = aux + a
            layer_states.append(st)

    with jax.named_scope("final_norm"):
        x = apply_norm(cparams["final_norm"], x, cfg.norm)
    if cache_len is None:
        return unembed_logits(cparams, cfg, x), aux
    return unembed_logits(cparams, cfg, x[:, -1]), aux, layer_states


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
