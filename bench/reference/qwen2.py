"""Plain reference of a Qwen2 decoder, with an optional image-prefix projector.

Straightforward ``jax.numpy`` in float32, written from the Qwen2 report
(arXiv:2407.10671) and the configuration file, and nothing of the program
under test: RMSNorm before attention and MLP, grouped-query attention with
biased Q/K/V projections and rotary embeddings on paired halves, a SwiGLU
MLP, a final RMSNorm, and logits against the tied embedding table.  An
image prefix (InternVL-style) is projected by RMSNorm, a linear map, GELU in
its tanh form and a second linear map, and put before the text embeddings;
the configuration file states that stand-in under ``assumed``.

Weights are the benchmark's own (``bench/weights/qwen2.py``), made from the
seed; layers are stacked on a leading axis and run one at a time under
``lax.scan``, each cast to float32 inside the loop, so that the reference
holds one layer in float32 at a time.  Every matmul runs at "highest"
precision: on a TPU a float32 matmul is otherwise rounded through bfloat16.

``quant`` rounds the operands of every weight matmul: ``None`` for the
reference itself; ``"fp8"`` for the control, the step below the bfloat16
that the configurations state (e4m3, each weight scaled as a whole and each
activation row scaled so that its largest magnitude maps to 448).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def sizes(config):
    m = config["model"]
    d = m["hidden_size"]
    h = m["num_attention_heads"]
    return {"d": d, "h": h, "kv": m["num_key_value_heads"], "dh": d // h,
            "eps": m["rms_norm_eps"], "theta": m["rope_theta"]}


def _fp8(x, axis):
    """e4m3 with the scale that maps the largest magnitude to 448."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax == 0, 1.0, amax / 448.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    return x + jax.lax.stop_gradient(q - x)        # straight-through


def matmul(spec, x, w, quant):
    """einsum ``spec`` of an activation and a weight, each rounded to
    ``quant`` first (fp8: the weight scaled as a whole, the activation by
    row)."""
    if quant == "fp8":
        x = _fp8(x, axis=-1)
        w = _fp8(w, axis=None)
    elif quant is not None:
        raise ValueError(quant)
    return jnp.einsum(spec, x, w, precision="highest")


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, positions, theta):
    """x (B, S, heads, dh): rotary embedding on paired halves."""
    dh = x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh))
    ang = positions[:, :, None].astype(F32) * jnp.asarray(freqs, F32)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(x, w, positions, z, quant):
    """One decoder layer on x (B, S, d) in float32."""
    w = jax.tree.map(lambda a: a.astype(F32), w)
    B, S, _ = x.shape
    a = w["attn"]
    h = rms_norm(x, w["ln1"]["scale"], z["eps"])
    q = matmul("bsd,dhk->bshk", h, a["wq"], quant) + a["bq"]
    k = matmul("bsd,dhk->bshk", h, a["wk"], quant) + a["bk"]
    v = matmul("bsd,dhk->bshk", h, a["wv"], quant) + a["bv"]
    q, k = rope(q, positions, z["theta"]), rope(k, positions, z["theta"])
    group = z["h"] // z["kv"]
    k = jnp.repeat(k, group, axis=2)                 # head i reads kv i // group
    v = jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhk,bthk->bhqt", q, k, precision="highest")
    s = s / np.sqrt(z["dh"])
    causal = positions[:, None, :, None] >= positions[:, None, None, :]
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("bhqt,bthk->bqhk", jax.nn.softmax(s, axis=-1), v,
                   precision="highest")
    x = x + matmul("bshk,hkd->bsd", o, a["wo"], quant)
    m = w["mlp"]
    h = rms_norm(x, w["ln2"]["scale"], z["eps"])
    g = matmul("bsd,df->bsf", h, m["wi_gate"], quant)
    u = matmul("bsd,df->bsf", h, m["wi_up"], quant)
    return x + matmul("bsf,fd->bsd", jax.nn.silu(g) * u, m["wo"], quant)


def hidden(weights, tokens, config, patch_embeds=None, quant=None,
           remat=False):
    """Final-normed hidden states (B, S, d): image prefix, then text."""
    z = sizes(config)
    table = weights["embed"]["table"]
    x = jnp.take(table, tokens, axis=0).astype(F32)
    if patch_embeds is not None:
        p = jax.tree.map(lambda a: a.astype(F32), weights["projector"])
        h = rms_norm(patch_embeds.astype(F32), p["ln"]["scale"], z["eps"])
        h = jax.nn.gelu(matmul("bpe,ed->bpd", h, p["w1"], quant),
                        approximate=True)
        x = jnp.concatenate([matmul("bpd,de->bpe", h, p["w2"], quant), x],
                            axis=1)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    body = lambda x, w: (layer(x, w, positions, z, quant), None)
    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, weights["units"]["b0"])
    return rms_norm(x, weights["final_norm"]["scale"].astype(F32), z["eps"])


def logits(weights, x, quant=None):
    """Logits (…, vocab) of final hidden states against the tied table."""
    return matmul("...d,vd->...v", x, weights["embed"]["table"].astype(F32),
                  quant)


def loss(weights, batch, config, quant=None, chunk=256):
    """Mean next-token cross-entropy over the labels that are >= 0; the
    logits are made ``chunk`` positions at a time (and made again for the
    gradient), so that a 152k vocabulary never holds a whole row's."""
    x = hidden(weights, batch["tokens"], config, batch.get("patch_embeds"),
               quant, remat=True)
    labels = batch["labels"]
    B, S, d = x.shape
    n = -(-S // chunk)
    pad = n * chunk - S
    x = jnp.pad(x, ((0, 0), (0, pad), (0, 0))).reshape(B, n, chunk, d)
    labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    labels = labels.reshape(B, n, chunk)

    @jax.checkpoint
    def nll(xc, lc):
        logp = jax.nn.log_softmax(logits(weights, xc, quant), axis=-1)
        ll = jnp.take_along_axis(logp, jnp.clip(lc, 0)[..., None], -1)[..., 0]
        return -(ll * (lc >= 0)).sum()

    total = jax.lax.map(lambda c: nll(*c), (x.swapaxes(0, 1),
                                            labels.swapaxes(0, 1))).sum()
    return total / jnp.maximum((labels >= 0).sum(), 1)
