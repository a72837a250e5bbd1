"""Train / prefill / decode step builders.

The train step composes: microbatch gradient accumulation (lax.scan),
mixed precision (f32 params, bf16 compute), remat policy (inside the model),
optional cross-pod compressed gradient reduction (partial-auto shard_map),
gradient clipping, and the optimizer update (ZeRO-1 sharded state).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, RunPolicy
from ..launch.mesh import shard_map
from ..models import api
from .optimizer import OptConfig, init_opt_state, opt_update
from . import compression

MOE_AUX_COEF = 0.01


def make_loss_fn(cfg: ModelConfig, policy: RunPolicy):
    def loss_fn(params, mb):
        logits, aux = api.forward(params, mb, cfg, policy)
        loss = api.lm_loss(logits, mb["labels"])
        if cfg.n_experts:
            loss = loss + MOE_AUX_COEF * aux[0]
        return loss, aux
    return loss_fn


def _split_microbatches(batch, n):
    def r(a):
        b = a.shape[0]
        assert b % n == 0, f"batch {b} not divisible by microbatches {n}"
        return a.reshape((n, b // n) + a.shape[1:])
    return jax.tree.map(r, batch)


def compute_grads(cfg, policy, params, batch):
    """Microbatched value+grad. Returns (loss, aux, grads[f32])."""
    loss_fn = make_loss_fn(cfg, policy)
    vgrad = jax.value_and_grad(loss_fn, has_aux=True)
    n = policy.n_microbatch
    if n <= 1:
        (loss, aux), grads = vgrad(params, batch)
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        return loss, aux, grads
    mbs = _split_microbatches(batch, n)
    g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

    def body(carry, mb):
        gsum, lsum, asum = carry
        (l, a), g = vgrad(params, mb)
        gsum = jax.tree.map(lambda s, gg: s + gg.astype(jnp.float32), gsum, g)
        return (gsum, lsum + l, asum + a), None

    (gsum, lsum, asum), _ = jax.lax.scan(
        body, (g0, jnp.zeros((), jnp.float32), jnp.zeros((2,), jnp.float32)), mbs)
    grads = jax.tree.map(lambda g: g / n, gsum)
    return lsum / n, asum / n, grads


def make_train_step(cfg: ModelConfig, policy: RunPolicy, opt: OptConfig,
                    mesh=None):
    """Returns train_step(params, opt_state, batch) -> (params, opt_state, metrics).

    When ``policy.grad_compress != 'none'`` and the mesh has a "pod" axis, the
    cross-pod gradient reduction is explicit (and compressed); otherwise the
    SPMD partitioner owns all reductions.
    """
    use_compress = (policy.grad_compress != "none" and mesh is not None
                    and "pod" in mesh.shape)

    if not use_compress:
        def train_step(params, opt_state, batch):
            loss, aux, grads = compute_grads(cfg, policy, params, batch)
            new_params, new_opt, stats = opt_update(opt, grads, opt_state, params)
            metrics = {"loss": loss, "moe_lb": aux[0], "moe_drop": aux[1], **stats}
            return new_params, new_opt, metrics
        return train_step

    from jax.sharding import PartitionSpec as P

    def _batch_specs(batch):
        return jax.tree.map(
            lambda a: P(*("pod",) + (None,) * (a.ndim - 1)), batch)

    def train_step(params, opt_state, batch):
        ef = opt_state.get("ef")

        def pod_body(params_, batch_, ef_):
            loss, aux, grads = compute_grads(cfg, policy, params_, batch_)
            ef_local = jax.tree.map(lambda e: e[0], ef_)   # strip pod-stack dim
            grads, new_ef = compression.reduce_grads(
                grads, ef_local, policy.grad_compress, axis="pod")
            new_ef = jax.tree.map(lambda e: e[None], new_ef)
            loss = jax.lax.pmean(loss, "pod")
            aux = jax.lax.pmean(aux, "pod")
            return loss, aux, grads, new_ef

        p_spec = jax.tree.map(lambda _: P(), params)
        ef_in = jax.tree.map(lambda _: P("pod"), ef) if ef is not None else P()
        # partial-manual shard_map: only "pod" is manual (we own its
        # collective and its wire format); data/model stay under SPMD.
        body = shard_map(
            pod_body, mesh=mesh,
            in_specs=(p_spec, _batch_specs(batch), ef_in),
            out_specs=(P(), P(), jax.tree.map(lambda _: P(), params),
                       jax.tree.map(lambda _: P("pod"), params)),
            axis_names={"pod"}, check_vma=False)
        if ef is None:
            n_pods = mesh.shape["pod"]
            ef = jax.tree.map(
                lambda p: jnp.zeros((n_pods,) + p.shape, jnp.float32), params)
        loss, aux, grads, new_ef = body(params, batch, ef)
        new_params, new_opt, stats = opt_update(
            opt, grads, {k: v for k, v in opt_state.items() if k != "ef"}, params)
        new_opt["ef"] = new_ef
        metrics = {"loss": loss, "moe_lb": aux[0], "moe_drop": aux[1], **stats}
        return new_params, new_opt, metrics

    return train_step


def make_init_opt(cfg: ModelConfig, policy: RunPolicy, opt: OptConfig,
                  mesh=None):
    def init(params):
        st = init_opt_state(opt, params)
        if (policy.grad_compress != "none" and mesh is not None
                and "pod" in mesh.shape):
            n_pods = mesh.shape["pod"]
            st["ef"] = jax.tree.map(
                lambda p: jnp.zeros((n_pods,) + p.shape, jnp.float32), params)
        return st
    return init


# ------------------------------------------------------------------- serving

def make_prefill_step(cfg: ModelConfig, policy: RunPolicy, cache_len: int):
    def prefill_step(params, batch):
        logits, aux, state = api.forward(params, batch, cfg, policy,
                                         cache_len=cache_len)
        return logits, state
    return prefill_step


def make_decode_step(cfg: ModelConfig, policy: RunPolicy):
    def dstep(params, state, batch):
        return api.decode_step(params, state, batch, cfg, policy)
    return dstep
