"""Training rows for a decoder, made on the device from the seed.

A mix file (``"kind": "lm_rows"``) gives the sequence length and whether an
image prefix comes first.  Step k's batch is drawn from the seed folded
with k, so every row of every step differs, and a step's rows can be made
again after the window (for the reference) without being kept.  Tokens are
uniform over the vocabulary; each label is the next token, and the image
prefix positions carry no label (-1).  Image-prefix embeddings are standard
normal, in the compute dtype.
"""
from __future__ import annotations


def feed(mix, config, batch, seed, shardings=None):
    """step -> {"tokens", "labels"[, "patch_embeds"]} on the device."""
    import jax
    import jax.numpy as jnp
    from lib.common import seed_key

    seq, vocab = mix["seq"], config["model"]["vocab_size"]
    vis = config.get("vision") if mix.get("image_prefix") else None
    prefix = vis["prefix_positions"] if vis else 0
    base = seed_key(seed)

    def make(step):
        k1, k2 = jax.random.split(jax.random.fold_in(base, step))
        row = jax.random.randint(k1, (batch, seq - prefix + 1), 0, vocab,
                                 jnp.int32)
        out = {"tokens": row[:, :-1], "labels": row[:, 1:]}
        if vis:
            out["labels"] = jnp.concatenate(
                [jnp.full((batch, prefix), -1, jnp.int32), out["labels"]], 1)
            out["patch_embeds"] = jax.random.normal(
                k2, (batch, prefix, vis["projector_in"]),
                jnp.float32).astype(jnp.bfloat16)
        return out

    return jax.jit(make, out_shardings=shardings)
