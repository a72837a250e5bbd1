"""The program's model configuration for a Qwen2-family decoder (and a
Qwen2 decoder under an image prefix), built from a configuration file.

The file holds the published sizes under the source's own keys and names
this file under ``program``; this is the one place that maps them onto the
program's ``ModelConfig``.  Another family brings a file of its own.
"""
from __future__ import annotations


def program_config(config):
    from repro.configs.base import ModelConfig
    m = config["model"]
    vis = config.get("vision")
    kw = {}
    if vis:
        kw = dict(frontend="vit", n_prefix=vis["prefix_positions"],
                  d_frontend=vis["projector_in"])
    if m.get("hidden_act", "silu") != "silu":
        raise ValueError(f"unsupported activation {m['hidden_act']!r}")
    return ModelConfig(
        name=config["name"], family="vlm" if vis else "dense",
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"],
        d_head=m["hidden_size"] // m["num_attention_heads"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        qkv_bias=True, rope_theta=float(m["rope_theta"]),
        tie_embeddings=m["tie_word_embeddings"], **kw)
