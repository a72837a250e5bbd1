"""Open-loop request schedule for a served model, from a mix's parameters.

Independent users send on a schedule whatever the server does, so the
loop is open.  A mix file (``bench/traffic/<mix>.json``, ``"kind":
"open_loop"``) gives lognormal prompt and output lengths (median, sigma,
clip), the lengths prompts are rounded up to, and the cell gives the rate.

Every seed serves the same work in another order: the ``n = rate x
seconds`` prompt lengths, output lengths and gaps between arrivals are the
quantiles at (i + 1/2) / n of their distributions (lognormal lengths,
exponential gaps, so Poisson arrivals), and the seed only permutes each of
them and draws the prompt tokens.  So runs on different seeds differ in
order and content, not in the amount of work.
"""
from __future__ import annotations

import statistics

import numpy as np


def _quantiles(n):
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(spec, n):
    z = np.array([statistics.NormalDist().inv_cdf(u) for u in _quantiles(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    x = np.clip(np.round(x), spec["min"], spec["max"]).astype(np.int64)
    buckets = spec.get("round_up_to")
    if buckets:
        b = np.asarray(sorted(buckets))
        x = b[np.searchsorted(b, x)]
    return x


def lengths(mix):
    """Every prompt length and the longest output the mix can send."""
    p = mix["prompt"]
    return (sorted(p["round_up_to"]) if p.get("round_up_to")
            else list(range(p["min"], p["max"] + 1))), mix["output"]["max"]


def schedule(mix, rate, seconds, seed, vocab):
    """[(due_s, prompt int32 array, max_new_tokens)], due in [0, seconds)."""
    n = max(int(round(rate * seconds)), 1)
    rng = np.random.default_rng(seed)
    prompt = rng.permutation(lognormal_lengths(mix["prompt"], n))
    output = rng.permutation(lognormal_lengths(mix["output"], n))
    gaps = rng.permutation(-np.log1p(-_quantiles(n)) / rate)
    due = np.cumsum(gaps) - gaps[0]
    out = []
    for i in range(n):
        toks = rng.integers(0, vocab, int(prompt[i]), dtype=np.int64)
        out.append((float(due[i]), toks.astype(np.int32), int(output[i])))
    return out
