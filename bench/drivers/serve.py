"""A served model under open-loop traffic: ``serve/engine.ServingEngine``.

Set-up makes the weights on the device from the seed, builds the engine
with the cell's slots, cache and policy, and warms every program the
traffic uses: a prefill for each prompt length of the mix, the update of
every slot, and the decode step.  The window then sends each request when
it is due, steps the engine while anything is in flight, and timestamps
each token when ``step`` hands it back.  Requests due in the window are
drained for up to a minute after it closes; one that has not finished by
then has failed.

``correct`` compares what the window served with the plain reference:
a sample of finished requests drawn from the seed, the longest among them,
each run once through the reference over its prompt and served tokens.
The number compared is the widest gap by which a served token's logit
lies below the reference's best at its position (greedy decoding serves the
best).  The window's outputs are also held to their exact lengths.
"""
from __future__ import annotations

import time

import numpy as np

DRAIN_S = 60.0


def _engine(files, seed, weights):
    from repro.serve.engine import ServingEngine
    from repro.configs.base import RunPolicy
    from lib import common
    srv = files["cell"]["serving"]
    cfg = common.config_module(files["config"], "program").program_config(
        files["config"])
    policy = RunPolicy(**files["cell"]["policy"])
    return ServingEngine(cfg, policy, weights, n_slots=srv["n_slots"],
                         cache_len=srv["cache_len"], seed=seed & 0x7FFFFFFF)


def warm_up(eng, mix_lengths, vocab):
    """Run every program the window will: a prefill of each prompt length,
    the update of every slot (one request per slot), and decode."""
    from repro.serve.engine import Request
    prompts, _ = mix_lengths
    rng = np.random.default_rng(0)
    n = max(eng.n_slots, len(prompts))
    reqs = [Request(rid=-1 - i, prompt=rng.integers(
        0, vocab, prompts[i % len(prompts)]).astype(np.int32),
        max_new_tokens=2) for i in range(n)]
    for r in reqs:
        eng.add_request(r)
    eng.run()
    eng.completed.clear()
    eng.stats = {k: 0 for k in eng.stats}


def serve_window(eng, sched, seconds, clock, spans, on_trace=None):
    """Drive the engine open-loop; returns per-request records: when each
    was due, sent, admitted into a lane (its prefill starts) and given each
    token, and the longest step."""
    from repro.serve.engine import Request
    reqs = [Request(rid=i, prompt=p, max_new_tokens=m)
            for i, (_, p, m) in enumerate(sched)]
    due = [d for d, _, _ in sched]
    times = [[] for _ in reqs]
    sent = [None] * len(reqs)
    admitted = [None] * len(reqs)
    insert = eng._insert

    def timed_insert(slot, req):
        admitted[req.rid] = clock()
        return insert(slot, req)
    eng._insert = timed_insert
    steps = []
    inflight = []
    t0 = clock()
    t_end = t0 + seconds
    i = 0
    while True:
        now = clock()
        if on_trace:
            on_trace(now - t0)
        while i < len(reqs) and t0 + due[i] <= now:
            with spans("arrive"):
                eng.add_request(reqs[i])
            sent[i] = now
            inflight.append(i)
            i += 1
        if not inflight:
            if i == len(reqs):
                if now >= t_end:
                    break
                time.sleep(min(t_end - now, 0.01))
            else:
                time.sleep(max(0.0, min(t0 + due[i] - now, 0.002)))
            continue
        if now > t_end + DRAIN_S:
            break
        t = clock()
        with spans("step"):
            eng.step()
        steps.append(clock() - t)
        t = clock()
        still = []
        for j in inflight:
            r = reqs[j]
            while len(times[j]) < len(r.out):
                times[j].append(t)
            if not r.done:
                still.append(j)
        inflight = still
    if on_trace:
        on_trace(None)
    del eng._insert                         # no cycle through the engine
    return {"t0": t0, "t_end": t_end, "t_last": clock(), "reqs": reqs,
            "due": [t0 + d for d in due], "sent": sent, "times": times,
            "admitted": admitted, "steps": steps}


def latency_metrics(rec):
    """Seconds: every request's time to first token and wait from due to
    admission, every gap between tokens, the generator's lateness; and the
    requests not finished by the end of the drain."""
    ttft, itl, wait = [], [], []
    failed = 0
    for r, due, ts, adm in zip(rec["reqs"], rec["due"], rec["times"],
                               rec["admitted"]):
        if not r.done:
            failed += 1
        if ts:
            ttft.append(ts[0] - due)
            itl.extend(np.diff(ts).tolist())
        if adm is not None:
            wait.append(adm - due)
    lateness = [s - d for s, d in zip(rec["sent"], rec["due"]) if s is not None]
    return {"ttft": ttft, "itl": itl, "wait": wait, "lateness": lateness,
            "failed": failed}


def pick_sample(rec, seed, n):
    """The finished request with the most served tokens, and ``n - 1``
    others drawn from the seed."""
    done = [r for r in rec["reqs"] if r.done and r.out]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.out), len(r.prompt)))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(seed)
    k = min(n - 1, len(rest))
    pick = rng.choice(len(rest), size=k, replace=False) if k else []
    return [longest] + [rest[j] for j in sorted(pick)]


def reference_gaps(weights, config, sample, pad_to, served_max, quant=None):
    """For each sampled request: the gap, at each served token, between the
    reference's best logit and the served token's (and, with ``quant``, the
    gap of the token that the lower precision puts first instead)."""
    import jax
    import jax.numpy as jnp
    from lib import common
    ref_model = common.config_module(config, "reference")

    @jax.jit
    def gaps(w, tokens, start, served):
        x = ref_model.hidden(w, tokens[None], config)[0]
        x = jax.lax.dynamic_slice_in_dim(x, start, served.shape[0])
        ref = ref_model.logits(w, x)
        best = ref.max(-1)
        if quant is None:
            pick = served
        else:
            xq = ref_model.hidden(w, tokens[None], config, quant=quant)[0]
            xq = jax.lax.dynamic_slice_in_dim(xq, start, served.shape[0])
            pick = jnp.argmax(ref_model.logits(w, xq, quant=quant), -1)
        got = jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
        return best - got

    out = []
    for r in sample:
        seq = np.concatenate([r.prompt, np.asarray(r.out[:-1], np.int32)])
        toks = np.zeros(pad_to, np.int32)
        toks[:len(seq)] = seq
        served = np.zeros(served_max, np.int32)
        served[:len(r.out)] = r.out
        g = np.asarray(gaps(weights, toks, len(r.prompt) - 1, served))
        out.append(g[:len(r.out)])
    return out


def setup(ctx, rate=None):
    """Weights, engine and warm programs; the window's schedule."""
    import jax
    import jax.numpy as jnp
    from lib import common
    from repro.models import api

    files, seed = ctx.files, ctx.seed
    cell, config, mix = files["cell"], files["config"], files["mix"]
    gen = ctx.generator(mix["kind"])
    W = common.config_module(config, "weights")
    cfg = common.config_module(config, "program").program_config(config)
    vocab = config["model"]["vocab_size"]
    with ctx.spans("setup"):
        weights = W.make(config, seed, jnp.bfloat16)
        W.check_layout(weights, api.abstract_params(cfg, jnp.bfloat16))
        eng = _engine(files, seed, weights)
        if ctx.trace:
            eng.prefill = ctx.spans.wrap(
                eng.prefill, "prefill",
                lambda p, b: {"len": int(b["tokens"].shape[1])})
            eng.decode = ctx.spans.wrap(
                eng.decode, "decode",
                lambda p, s, b: {"active": [
                    int(eng.slot_pos[i]) + 1
                    for i, r in enumerate(eng.slot_req) if r is not None]})
            eng._update = ctx.spans.wrap(eng._update, "admit")
        lengths = gen.lengths(mix)
        warm_up(eng, lengths, vocab)
        sched = gen.schedule(mix, rate or cell["serving"]["rate"],
                             ctx.seconds, seed, vocab)
        jax.block_until_ready(eng.state)
    return {"weights": weights, "eng": eng, "sched": sched,
            "lengths": lengths}


def window(ctx, st):
    ctx.window_starts()
    rec = serve_window(st["eng"], st["sched"], ctx.seconds,
                       time.perf_counter, ctx.spans, ctx.tracer)
    ctx.window_ends()
    ctx.memory_peak()
    rec["decode_steps"] = st["eng"].stats["decode_steps"]
    st["eng"].state = None                  # free the cache for the check
    del st["eng"]
    return rec


def check(ctx, st, rec, quant=None):
    """The numbers compared, each as (value, limit).  With ``quant`` the
    reference at that precision stands in for the served tokens (the
    control)."""
    chk = ctx.files["cell"]["correct"]
    prompts, out_max = st["lengths"]
    sample = pick_sample(rec, ctx.seed, chk["sample_requests"])
    gaps = reference_gaps(st["weights"], ctx.files["config"], sample,
                          max(prompts) + out_max, out_max, quant)
    worst = max((float(g.max()) for g in gaps), default=float("inf"))
    wrong = sum(1 for r in rec["reqs"]
                if r.done and len(r.out) != r.max_new_tokens)
    return {"max_logit_gap": (worst, chk["max_logit_gap"]),
            "wrong_lengths": (wrong, 0),
            "sampled_tokens": (sum(len(g) for g in gaps), None)}


def _ms(v, p):
    return float(np.percentile(v, p)) * 1e3 if v else None


def run(ctx):
    st = setup(ctx)
    rec = window(ctx, st)
    lat = latency_metrics(rec)
    checks = check(ctx, st, rec)
    steps = rec["steps"]
    return {
        "attempted": len(rec["reqs"]),
        "failed": lat["failed"],
        "checks": checks,
        "metrics": {"ttft_p95_ms": _ms(lat["ttft"], 95),
                    "itl_p95_ms": _ms(lat["itl"], 95)},
        "queue_wait_s": lat["wait"],
        "notes": [f"requests {len(rec['reqs'])} failed {lat['failed']}; "
                  f"ttft p50 {_ms(lat['ttft'], 50)} ms, p95 "
                  f"{_ms(lat['ttft'], 95)} ms; itl p50 {_ms(lat['itl'], 50)} "
                  f"ms; queue wait p50 {_ms(lat['wait'], 50)} ms; generator "
                  f"late p95 {_ms(lat['lateness'], 95)} ms; decode steps "
                  f"{rec['decode_steps']}; longest step "
                  f"{max(steps, default=0.0) * 1e3!r} ms, steps over 250 ms "
                  f"{sum(1 for t in steps if t > 0.25)}"],
    }


def calibrate(ctx, rate=None, control=None):
    """One seed's readings: the program's numbers and, with ``control``,
    the control's; and the latencies, for a sweep of the rate."""
    st = setup(ctx, rate)
    rec = window(ctx, st)
    lat = latency_metrics(rec)
    out = {"program": {k: v for k, (v, _) in check(ctx, st, rec).items()},
           "requests": len(rec["reqs"]), "failed": lat["failed"],
           "backlog_s": rec["t_last"] - rec["t_end"],
           "ttft_ms": [round(t * 1e3, 3) for t in lat["ttft"]],
           "tpot_ms": [round((ts[-1] - ts[0]) / (len(ts) - 1) * 1e3, 3)
                       for ts in rec["times"] if len(ts) > 1],
           "itl_p95_ms": _ms(lat["itl"], 95),
           "ttft_p95_ms": _ms(lat["ttft"], 95),
           "queue_wait_p50_ms": _ms(lat["wait"], 50),
           "decode_steps": rec["decode_steps"],
           "longest_step_ms": max(rec["steps"], default=0.0) * 1e3,
           "gc": ctx.gc_in_window(), "heartbeat": ctx.heartbeat.summary()}
    if control:
        out["control"] = {k: v for k, (v, _) in
                          check(ctx, st, rec, quant=control).items()}
    return out
