#!/usr/bin/env python3
"""Smoke run of the system's main paths on the chip, at qwen2-1.5b's widths.

  python3 chip_smoke.py             # one chip: device, kernels, measure,
                                    # train, serve
  python3 chip_smoke.py --chips 4   # four chips: full-depth training under
                                    # fsdp, checked against tp

Every phase goes through the entry points a user calls, with weights and
data made from ``--seed``.  The first phase that fails ends the run with a
non-zero exit and no result line.  A passing run prints, as its last line,
one JSON object naming the device.  The script is one process and starts
no other; it keeps JAX's compile cache where ``launch/compile_cache`` says.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "qwen2-1.5b"
RUN_DIR = os.path.join(ROOT, "runs", "chip_smoke")

# Kernel tolerances, as max|kernel - ref| / max|ref|.  The reference is
# kernels/ref.py in f32 at "highest" matmul precision on the same
# bf16-rounded inputs, so what is left is the kernel's own rounding.
# - bf16 outputs (flash attention forward and backward, flash decode) are
#   rounded to 2**-9 of their size, and the kernels round the softmax
#   weights (and, backward, their gradients) to bf16 for the MXU, which
#   adds about as much again: 2e-2 leaves room, while a wrong mask, block
#   offset or softmax rescale moves the result by O(1).
# - rglru_scan takes f32 gates (the model computes them in f32, in bf16
#   runs too) and runs the reference's own recurrence a_t * h + b_t,
#   elementwise, in the same order: only rounding of one multiply-add can
#   differ, so 1e-5.
# - rwkv6_wkv reassociates the recurrence chunk-wise and runs its f32
#   contractions on the MXU at full precision; its outputs are f32, so
#   1e-3 leaves room for the reassociation while one bf16-rounded pass
#   (about 4e-3) fails.
KERNEL_TOL = {"flash_fwd": 2e-2, "flash_bwd": 2e-2, "flash_decode": 2e-2,
              "rglru_scan": 1e-5, "rwkv6_wkv": 1e-3}

# Serving: logits of prefill (flash-attention kernel) followed by cache
# decode, against one full forward pass over prompt plus output with plain
# attention, in f32 at "highest" precision, as max|diff| / max|ref|.  The
# comparison runs in f32 because random weights make this stack chaotic:
# on the CPU at width 256 and 28 layers, bf16 and f32 forward passes of
# the same weights differ by 18-24% of max|logit|, while the engine in f32
# matches the f32 forward exactly.  So 1e-2 leaves f32 reassociation
# (online softmax, blocked matmuls) ample room, and a bf16 pass anywhere,
# a cache written at the wrong slot or a position off by one all fail.
SERVE_TOL = 1e-2

# fsdp and tp run the same bf16 step with their reductions split
# differently; the f32 mean loss over the batch may differ by about one
# bf16 rounding of the per-token terms, 2**-8 of the loss.
LOSS_TOL = 2.0 ** -8


@dataclasses.dataclass(frozen=True)
class Plan:
    """Sizes of every phase (``PLAN`` is the chip run)."""
    arch: str = ARCH
    # the one-chip training cut: 8 of 28 layers at batch 1 x seq 2048.  An
    # AOT compile for a described v5e puts its memory_analysis() peak at
    # 13.55 GiB of the chip's 16 GiB (10 layers: 15.12 GiB; AdamW state is
    # 16 B per parameter, so 28 layers need about 24.6 GB)
    train_layers: int = 8
    train_seq: int = 2048
    train_batch: int = 1
    train_steps: int = 5
    prefill: tuple = (2048, 1)       # (seq, batch) of the measured cells
    decode: tuple = (2048, 4)        # (cache length, batch)
    attn_heads: tuple = (12, 2, 128)     # flash kernels: H, KVH, D
    attn_seq: int = 2048
    decode_cache: int = 2048
    rglru_width: int = 2560          # recurrentgemma-2b
    rwkv_heads: tuple = (64, 64)     # rwkv6-7b: heads, head size
    serve_prompts: tuple = (96, 200)
    serve_requests: int = 8
    serve_slots: int = 4
    serve_new: int = 16
    serve_cache: int = 512


PLAN = Plan()


class PhaseFailed(RuntimeError):
    pass


def check(ok, msg):
    if not ok:
        raise PhaseFailed(msg)


def rel_err(got, want):
    import jax.numpy as jnp
    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


# ------------------------------------------------------------------ phases

def phase_device(n_chips):
    import jax
    from repro import hw
    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU: JAX's first device is on {devs[0].platform!r}")
    chip = hw.chip_spec(devs[0].device_kind)
    check(len(devs) >= n_chips, f"{len(devs)} chips, {n_chips} needed")
    print(f"device: {len(devs)} x {devs[0].device_kind} ({chip.name}: "
          f"{chip.peak_flops_bf16:.4g} FLOP/s bf16, {chip.hbm_bw:.4g} B/s, "
          f"{chip.hbm_bytes / 2**30:.4g} GiB)", flush=True)
    return devs


def phase_kernels(plan, seed):
    """Each Pallas kernel compiled by Mosaic at real widths vs kernels/ref."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref
    from repro.kernels.decode_attention import flash_decode
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.rglru_scan import rglru_scan
    from repro.kernels.rwkv6_kernel import rwkv6_wkv

    bf16, f32 = jnp.bfloat16, jnp.float32
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 32))

    def normal(shape, dtype=bf16):
        return jax.random.normal(next(keys), shape, f32).astype(dtype)

    def highest(fn, *args):
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn)(*[a.astype(f32) if a.dtype == bf16 else a
                                 for a in args])

    errs = {}
    H, KVH, D = plan.attn_heads
    S = plan.attn_seq
    q, k, v = normal((1, H, S, D)), normal((1, KVH, S, D)), normal((1, KVH, S, D))
    w = normal((1, H, S, D))

    def fa(q, k, v):
        return flash_attention(q, k, v, None, 0, None, None, False)

    def fa_loss(fn):
        return lambda q, k, v, w: (fn(q, k, v).astype(f32) * w).sum()

    errs["flash_fwd"] = [rel_err(jax.jit(fa)(q, k, v),
                                 highest(ref.flash_attention_ref, q, k, v))]
    grads = jax.jit(jax.grad(fa_loss(fa), argnums=(0, 1, 2)))(q, k, v, w)
    want = highest(jax.grad(fa_loss(ref.flash_attention_ref),
                            argnums=(0, 1, 2)), q, k, v, w)
    errs["flash_bwd"] = [rel_err(g, r) for g, r in zip(grads, want)]

    B, T = 4, plan.decode_cache
    qd, kd, vd = normal((B, H, D)), normal((B, KVH, T, D)), normal((B, KVH, T, D))
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    pos = pos.at[:, T - T // 4:].set(-1)           # unwritten slots
    qpos = jnp.array([T - T // 4 - 1, T // 2, 7, 0], jnp.int32)
    got = jax.jit(lambda *a: flash_decode(*a, interpret=False))(
        qd, kd, vd, pos, qpos)
    errs["flash_decode"] = [rel_err(got, highest(ref.flash_decode_ref, qd,
                                                 kd, vd, pos, qpos))]

    W = plan.rglru_width
    a = jax.random.uniform(next(keys), (2, S, W), f32, 0.5, 0.999)
    b = normal((2, S, W), f32)
    got = jax.jit(lambda a, b: rglru_scan(a, b, interpret=False))(a, b)
    errs["rglru_scan"] = [rel_err(got, highest(ref.rglru_scan_ref, a, b))]

    RH, hs = plan.rwkv_heads
    r, kk, vv = (normal((1, RH, S, hs)) for _ in range(3))
    w_log = (-jnp.exp(normal((1, RH, S, hs), f32))).astype(bf16)
    u = normal((RH, hs))
    got = jax.jit(lambda *x: rwkv6_wkv(*x, interpret=False))(r, kk, vv,
                                                            w_log, u)
    errs["rwkv6_wkv"] = [rel_err(got, highest(ref.rwkv6_wkv_ref, r, kk, vv,
                                              w_log, u))]

    bad = []
    for name, es in errs.items():
        tol = KERNEL_TOL[name]
        print(f"kernel {name}: max_rel_err {' '.join(f'{e:.3e}' for e in es)}"
              f" tol {tol:.0e} {'ok' if max(es) <= tol else 'FAIL'}",
              flush=True)
        if not max(es) <= tol:
            bad.append(name)
    check(not bad, f"kernels outside tolerance: {bad}")


def phase_measure(plan, devices):
    """The search's measurement path on a (1, 1) ("data", "model") mesh."""
    import numpy as np
    from jax.sharding import Mesh
    from repro.configs.base import ShapeSpec, get_config
    from repro.core.engine import Engine
    from repro.core.searchspace import SearchSpace

    full = get_config(plan.arch)
    cut = dataclasses.replace(full, name=f"{full.name}-{plan.train_layers}l",
                              n_layers=plan.train_layers)
    shapes = {"train": ShapeSpec("train", "train", plan.train_seq,
                                 plan.train_batch),
              "prefill": ShapeSpec("prefill", "prefill", *plan.prefill),
              "decode": ShapeSpec("decode", "decode", *plan.decode)}
    space = SearchSpace({full.name: full, cut.name: cut}, shapes)
    mesh = Mesh(np.asarray(devices[:1]).reshape(1, 1), ("data", "model"))
    eng = Engine(space, {"single": mesh}, persistent_cache=False)
    base = {"mesh": "single", "remat": "dots", "n_microbatch": 1,
            "params_f32": True, "zero1": True, "optimizer": "adamw",
            "grad_compress": "none", "preset": "fsdp", "seq_shard": True,
            "cache_shard": True, "vocab_shard": True, "scan_layers": True,
            "attn_impl": "auto", "capacity_factor": 1.25}
    try:
        for kind, cfg in (("train", cut), ("prefill", full),
                          ("decode", full)):
            for pallas in (False, True):
                point = {**base, "arch": cfg.name, "shape": kind,
                         "use_pallas": pallas}
                m = eng.measure_full(point)
                check(m is not None and not eng.failures,
                      f"measure {kind} use_pallas={pallas}: {eng.failures}")
                counters = {f"perf.{k}": v for k, v in m.perf.items()}
                counters.update({f"diag.{k}": v for k, v in m.diag.items()})
                print(f"measure {cfg.name} {kind} {shapes[kind].seq_len}x"
                      f"{shapes[kind].global_batch} use_pallas={pallas}: "
                      f"compile {m.compile_s:.2f} s, memory_analysis peak "
                      f"{m.memory['peak_bytes'] / 2**30:.3f} GiB, "
                      f"tpu_custom_call x{m.tpu_custom_calls}, counters "
                      f"{json.dumps(counters, sort_keys=True)}", flush=True)
                if pallas and kind in ("train", "prefill"):
                    check(m.tpu_custom_calls > 0,
                          f"measure {kind}: use_pallas compiled no kernel")
    finally:
        eng.close()
    print(f"measure: engine {json.dumps(eng.stats(), sort_keys=True)}",
          flush=True)


def phase_train(plan):
    """launch/train.py's main, on a cut of the model sized to one chip."""
    from repro.launch import train
    ckpt = os.path.join(RUN_DIR, "train_ckpt")
    shutil.rmtree(ckpt, ignore_errors=True)
    print(f"train: {plan.arch} cut to {plan.train_layers} layers at "
          f"published widths, batch {plan.train_batch} x seq "
          f"{plan.train_seq}, AdamW, bf16 compute", flush=True)
    out = train.main(["--arch", plan.arch,
                      "--layers", str(plan.train_layers),
                      "--seq", str(plan.train_seq),
                      "--batch", str(plan.train_batch), "--microbatch", "1",
                      "--steps", str(plan.train_steps),
                      "--ckpt-dir", ckpt,
                      "--ckpt-every", str(plan.train_steps)])
    losses = out["losses"]
    print(f"train: losses {losses} step_s "
          f"{[round(s, 4) for s in out['step_s']]} bytes_in_use "
          f"{out['bytes_in_use']}", flush=True)
    check(len(losses) == plan.train_steps
          and all(math.isfinite(x) for x in losses),
          f"train: losses {losses}")
    check(os.path.isdir(os.path.join(ckpt, f"step_{plan.train_steps}")),
          f"train: no checkpoint in {ckpt}")


def phase_serve(plan, seed):
    """serve/engine.ServingEngine over the full-depth model, Pallas on."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs.base import RunPolicy, get_config
    from repro.models import api
    from repro.serve.engine import Request, ServingEngine

    cfg = get_config(plan.arch)
    params = jax.jit(lambda key: api.init(cfg, key, jnp.bfloat16))(
        jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    lens = plan.serve_prompts
    prompts = [rng.integers(0, cfg.vocab_size, lens[i % len(lens)]
                            ).astype(np.int32)
               for i in range(plan.serve_requests)]

    def serve(dtype, prompts):
        eng = ServingEngine(cfg, RunPolicy(use_pallas=True, remat="none",
                                           dtype=dtype),
                            params, n_slots=plan.serve_slots,
                            cache_len=plan.serve_cache, seed=seed)
        reqs = [Request(rid=i, prompt=p, max_new_tokens=plan.serve_new)
                for i, p in enumerate(prompts)]
        reqs[0].logits = []
        for r in reqs:
            eng.add_request(r)
        t0 = time.perf_counter()
        done = eng.run()
        print(f"serve {dtype}: {cfg.name} ({cfg.n_layers} layers), "
              f"{len(done)}/{len(reqs)} requests on {plan.serve_slots} "
              f"slots, prompts {sorted({len(p) for p in prompts})}, "
              f"{eng.stats} in {time.perf_counter() - t0:.2f} s "
              f"(compiles included)", flush=True)
        check(len(done) == len(reqs)
              and all(r.done and len(r.out) == r.max_new_tokens
                      for r in reqs),
              f"serve {dtype}: {[(r.rid, r.done, len(r.out)) for r in reqs]}")
        return reqs[0]

    def full_forward(req, dtype):
        tokens = np.concatenate([req.prompt,
                                 np.asarray(req.out[:-1], np.int32)])
        policy = RunPolicy(attn_impl="plain", remat="none", dtype=dtype)
        fn = jax.jit(lambda p, t: api.forward(p, {"tokens": t}, cfg,
                                              policy)[0])
        return fn(params, jnp.asarray(tokens)[None])[0, len(req.prompt) - 1:]

    r_bf16 = serve("bf16", prompts)
    with jax.default_matmul_precision("highest"):
        r_f32 = serve("f32", prompts[:1])
        ref = full_forward(r_f32, "f32")
        ref_of_bf16 = full_forward(r_bf16, "f32")
    err = rel_err(np.stack(r_f32.logits), ref)
    print(f"serve: request 0 prefill+decode logits ({len(r_f32.logits)} "
          f"tokens) vs full forward, f32: max_rel_err {err:.3e} (tol "
          f"{SERVE_TOL:.0e}); the bf16 run's vs f32: "
          f"{rel_err(np.stack(r_bf16.logits), ref_of_bf16):.3e} (not "
          f"gated, see SERVE_TOL)", flush=True)
    check(err <= SERVE_TOL, f"serve: logits off by {err:.3e}")


def run_one_chip(plan, seed):
    devices = phase_device(1)
    phase_kernels(plan, seed)
    phase_measure(plan, devices)
    phase_train(plan)
    phase_serve(plan, seed)
    return devices


def run_four_chips(plan, seed):
    """Full-depth training sharded over four chips, fsdp against tp."""
    from repro.configs.base import get_config
    from repro.models import api
    from repro.launch import train
    devices = phase_device(4)
    state_bytes = api.n_params(get_config(plan.arch)) * 12  # f32 + AdamW
    first = {}
    for preset in ("fsdp", "tp"):
        out = train.main(["--arch", plan.arch, "--preset", preset,
                          "--model-axis", "4", "--seq", str(plan.train_seq),
                          "--batch", str(plan.train_batch),
                          "--microbatch", "1", "--steps", "3",
                          "--seed", str(seed), "--ckpt-every", "0"])
        losses, used = out["losses"], out["bytes_in_use"]
        print(f"train4 {preset}: losses {losses} step_s "
              f"{[round(s, 4) for s in out['step_s']]} bytes_in_use per "
              f"device {used} (whole params + AdamW state: {state_bytes})",
              flush=True)
        check(len(losses) == 3 and all(math.isfinite(x) for x in losses),
              f"train4 {preset}: losses {losses}")
        check(all(b is not None and b < state_bytes / 2 for b in used),
              f"train4 {preset}: a device holds half the model or more")
        first[preset] = losses[0]
    diff = abs(first["fsdp"] - first["tp"])
    tol = LOSS_TOL * abs(first["tp"])
    print(f"train4: first-step loss fsdp {first['fsdp']!r} tp "
          f"{first['tp']!r}, |diff| {diff:.3e} tol {tol:.3e}", flush=True)
    check(diff <= tol, "train4: fsdp and tp disagree")
    return devices


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch import compile_cache
    from repro.runtime.spans import CompileLog
    cache_dir = compile_cache.enable()
    log = CompileLog().install()
    t0 = time.perf_counter()
    run = run_four_chips if args.chips == 4 else run_one_chip
    devices = run(PLAN, args.seed)
    print(f"compile: {log.seconds:.2f} s backend compile over "
          f"{log.programs} programs, {log.cache_hits} persistent-cache hits "
          f"({cache_dir}); total {time.perf_counter() - t0:.2f} s",
          flush=True)
    import jax
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    try:
        main()
    except PhaseFailed as e:
        print(f"FAILED: {e}", file=sys.stderr)
        sys.exit(1)
