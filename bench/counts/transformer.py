"""Operations and bytes a decoder's work needs, from the shapes of the calls.

Counted for the algorithm, whatever implements it: causal attention is its
lower triangle (diagonal included), a matmul of an m x k by a k x n operand
is 2mkn operations, recomputation is not counted, and bytes are what must
cross HBM at least once (weights once per call, activations at their
inputs and outputs).  All functions take the configuration file's ``model``
(and ``vision``) sizes, so they hold for any configuration of this family.
"""
from __future__ import annotations


def _z(config):
    m = config["model"]
    d, h = m["hidden_size"], m["num_attention_heads"]
    return (m["num_hidden_layers"], d, h, m["num_key_value_heads"], d // h,
            m["intermediate_size"], m["vocab_size"])


def layer_matmul_params(config):
    """Weights one token multiplies by in one layer (biases and norms aside)."""
    L, d, h, kv, dh, f, V = _z(config)
    return d * h * dh + 2 * d * kv * dh + h * dh * d + 3 * d * f


def unembed_params(config):
    L, d, h, kv, dh, f, V = _z(config)
    return V * d


def projector_params(config):
    vis = config.get("vision")
    if not vis:
        return 0
    d = config["model"]["hidden_size"]
    return vis["projector_in"] * d + d * d


def causal_attention(config, seq, backward=False):
    """(operations, bytes) of one sequence's causal attention over all
    layers, forward or, with ``backward``, its gradient (dV, dP, dQ, dK).

    Bytes: Q, K, V read and O written (forward); Q, K, V, O and dO read and
    dQ, dK, dV written (backward), at 2 bytes each.
    """
    L, d, h, kv, dh, f, V = _z(config)
    pairs = seq * (seq + 1) // 2
    ops = L * 4 * h * dh * pairs * (2 if backward else 1)
    q, k = seq * h * dh, seq * kv * dh
    elems = (3 * q + 4 * k) if backward else (2 * q + 2 * k)
    return ops, L * elems * 2


def train_ops_per_sequence(config, seq):
    """Forward and backward operations of one training sequence of ``seq``
    positions: 6 per weight per position (2 forward, 4 backward), the
    unembedding at the positions that carry a label, the projector at the
    image-prefix positions, and causal attention (forward and backward)."""
    L = config["model"]["num_hidden_layers"]
    vis = config.get("vision")
    prefix = vis["prefix_positions"] if vis else 0
    ops = 6 * (L * layer_matmul_params(config) * seq
               + unembed_params(config) * (seq - prefix)
               + projector_params(config) * prefix)
    fwd, _ = causal_attention(config, seq)
    bwd, _ = causal_attention(config, seq, backward=True)
    return ops + fwd + bwd


def prefill_ops(config, seq):
    """One prompt of ``seq`` tokens: every layer at every position, causal
    attention, and the unembedding of the last position only."""
    L = config["model"]["num_hidden_layers"]
    attn, _ = causal_attention(config, seq)
    return 2 * (L * layer_matmul_params(config) * seq
                + unembed_params(config)) + attn


def decode_bytes(config, positions, weight_bytes=2, cache_bytes=2):
    """Bytes one batched decode step must read: every weight once (the tied
    table too, for the unembedding) and, for each lane, the keys and values
    of the ``positions[i]`` cache positions it attends."""
    L, d, h, kv, dh, f, V = _z(config)
    weights = (L * layer_matmul_params(config) + unembed_params(config)) \
        * weight_bytes
    kv_per_position = L * 2 * kv * dh * cache_bytes
    return weights + kv_per_position * sum(positions)


def decode_ops(config, positions):
    """One batched decode step: every lane's token through every layer and
    the unembedding, and its attention over the ``positions[i]`` cache
    positions it attends (QK and PV, 4 x head size a position and head)."""
    L, d, h, kv, dh, f, V = _z(config)
    per_token = 2 * (L * layer_matmul_params(config) + unembed_params(config))
    return per_token * len(positions) + L * 4 * h * dh * sum(positions)
