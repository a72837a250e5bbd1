"""Batched serving engine: per-request prefill + slot-based continuous decode.

A fixed pool of ``n_slots`` decode lanes; each incoming request is prefilled
(cache built at its own length), inserted into a free lane of the batched
cache, and advanced by the shared batched decode step.  Lanes free up on EOS
or max_new_tokens — continuous-batching-lite, the serving pattern the
decode_* shape cells lower.

Each step and its phases are spans of ``runtime/spans`` (``serve.*``; off
unless the engine's tracer is on, by default while JAX's profiler records):
``serve.enqueue`` (no length, in ``add_request``), ``serve.step``, and in
it ``serve.admit`` (``serve.prefill``, ``serve.slot_update``, and
``serve.first_token``, the blocking fetch of the request's first token),
``serve.upload`` (the lanes' tokens and positions), ``serve.decode`` (the
dispatch), ``serve.sample`` (the blocking fetch of the next tokens) and
``serve.lanes`` (the lanes' bookkeeping).  A request's spans carry its id.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig, RunPolicy
from ..models import api
from ..runtime import spans as spans_mod
from ..train.train_step import make_decode_step, make_prefill_step


def sample_logits(logits, key, temperature: float = 0.0):
    if temperature <= 0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits / temperature).astype(jnp.int32)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray          # (S,) int32
    max_new_tokens: int = 16
    eos_id: int = -1            # -1: never
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    logits: list | None = None  # a list here collects each token's logits


def _update_slot(state, state1, slot):
    """Write single-request state1 (batch 1) into lane ``slot`` of state.

    Both are one tree per layer (``models/transformer.model_state_shapes``),
    every leaf ``(B, ...)``.
    """
    return jax.tree.map(
        lambda dst, src: jax.lax.dynamic_update_slice_in_dim(
            dst, src.astype(dst.dtype), slot, axis=0),
        state, state1)


class ServingEngine:
    def __init__(self, cfg: ModelConfig, policy: RunPolicy, params,
                 n_slots: int = 4, cache_len: int = 256, seed: int = 0,
                 temperature: float = 0.0, spans: spans_mod.Spans | None = None):
        if cfg.frontend == "encodec":
            raise NotImplementedError("serving engine drives token-stream archs")
        self.cfg, self.policy, self.params = cfg, policy, params
        self.n_slots, self.cache_len = n_slots, cache_len
        self.temperature = temperature
        self.key = jax.random.PRNGKey(seed)
        self.prefill = jax.jit(make_prefill_step(cfg, policy, cache_len))
        # the state donated: decode writes each layer's cache in place
        self.decode = jax.jit(make_decode_step(cfg, policy), donate_argnums=1)
        # the slot traced: one program writes any lane
        self._update = jax.jit(_update_slot)
        self.state = api.init_state(cfg, n_slots, cache_len,
                                    jnp.bfloat16 if policy.dtype == "bf16"
                                    else jnp.float32)
        self.slot_req: list[Request | None] = [None] * n_slots
        self.slot_pos = np.zeros(n_slots, np.int64)
        self.slot_last_tok = np.zeros(n_slots, np.int64)
        self.pending: list[Request] = []
        self.completed: list[Request] = []
        self.stats = {"prefills": 0, "decode_steps": 0, "tokens_out": 0}
        self.spans = spans_mod.PROGRAM if spans is None else spans

    # ------------------------------------------------------------------ admin
    def add_request(self, req: Request):
        self.spans.mark("serve.enqueue", rid=req.rid)
        self.pending.append(req)

    def _insert(self, slot: int, req: Request):
        sp = self.spans
        with sp("serve.admit", rid=req.rid, slot=slot):
            with sp("serve.prefill", rid=req.rid, len=len(req.prompt)):
                prompt = jnp.asarray(req.prompt, jnp.int32)[None, :]
                logits, state1 = self.prefill(self.params, {"tokens": prompt})
            with sp("serve.slot_update", rid=req.rid, slot=slot):
                self.state = self._update(self.state, state1, slot)
            with sp("serve.first_token", rid=req.rid):
                self.key, k = jax.random.split(self.key)
                tok = int(sample_logits(logits, k, self.temperature)[0])
            sp.count("serve.prompt_tokens", len(req.prompt))
            req.out.append(tok)
            if req.logits is not None:
                req.logits.append(np.asarray(logits[0]))
            self.slot_req[slot] = req
            self.slot_pos[slot] = len(req.prompt)
            self.slot_last_tok[slot] = tok
            self.stats["prefills"] += 1

    def _free_slots(self):
        return [i for i, r in enumerate(self.slot_req) if r is None]

    # ------------------------------------------------------------------- step
    def step(self):
        """Admit pending requests, run one batched decode step."""
        sp = self.spans
        sp.tick()
        with sp("serve.step"):
            for slot in self._free_slots():
                if not self.pending:
                    break
                self._insert(slot, self.pending.pop(0))
            active = [i for i, r in enumerate(self.slot_req) if r is not None]
            if not active:
                return False
            with sp("serve.upload"):
                toks = jnp.asarray(self.slot_last_tok, jnp.int32)[:, None]
                pos = jnp.asarray(self.slot_pos, jnp.int32)
            with sp("serve.decode", active=len(active)):
                logits, self.state = self.decode(
                    self.params, self.state, {"tokens": toks, "position": pos})
            self.stats["decode_steps"] += 1
            sp.count("serve.lanes_decoded", len(active))
            with sp("serve.sample"):
                self.key, k = jax.random.split(self.key)
                nxt = np.asarray(sample_logits(logits, k, self.temperature))
            with sp("serve.lanes"):
                for i in active:
                    req = self.slot_req[i]
                    tok = int(nxt[i])
                    req.out.append(tok)
                    if req.logits is not None:
                        req.logits.append(np.asarray(logits[i]))
                    self.stats["tokens_out"] += 1
                    self.slot_pos[i] += 1
                    self.slot_last_tok[i] = tok
                    hit_eos = (req.eos_id >= 0 and tok == req.eos_id)
                    if hit_eos or len(req.out) >= req.max_new_tokens \
                            or self.slot_pos[i] >= self.cache_len - 1:
                        req.done = True
                        self.completed.append(req)
                        self.slot_req[i] = None
            return True

    def run(self, max_steps: int = 1000):
        steps = 0
        while (self.pending or any(r is not None for r in self.slot_req)) \
                and steps < max_steps:
            self.step()
            steps += 1
        return self.completed
