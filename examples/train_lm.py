"""End-to-end training driver: data pipeline -> train loop -> checkpoints ->
fault-tolerance hooks (heartbeat/straggler/elastic) -> metrics log.

Default preset trains a ~20M-param llama-family model for 200 steps on CPU
(~10 min); --preset 100m gives the ~100M-param configuration used on real
accelerators (same code path; slower on this CPU container).

  PYTHONPATH=src python examples/train_lm.py --steps 200
  PYTHONPATH=src python examples/train_lm.py --preset 100m --steps 300
  PYTHONPATH=src python examples/train_lm.py --resume   # continue from ckpt
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RunPolicy, ShapeSpec
from repro.launch import compile_cache
from repro.ckpt.checkpoint import CheckpointManager
from repro.data.pipeline import Prefetcher, SyntheticLM
from repro.models import api
from repro.runtime.elastic import ElasticController
from repro.train.optimizer import OptConfig
from repro.train.train_step import make_init_opt, make_train_step

PRESETS = {
    "20m": ModelConfig(name="llama-20m", family="dense", n_layers=6,
                       d_model=384, n_heads=6, n_kv_heads=2, d_head=64,
                       d_ff=1024, vocab_size=8192, rope_theta=1e4),
    "100m": ModelConfig(name="llama-100m", family="dense", n_layers=12,
                        d_model=768, n_heads=12, n_kv_heads=4, d_head=64,
                        d_ff=2048, vocab_size=32000, rope_theta=1e4),
}


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="20m", choices=list(PRESETS))
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--ckpt-dir", default=os.path.join(
        compile_cache.ROOT, "runs", "train_lm", "ckpt"))
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    cfg = PRESETS[args.preset]
    shape = ShapeSpec("train", "train", args.seq, args.batch)
    policy = RunPolicy(remat="dots", dtype="f32", n_microbatch=2)
    opt = OptConfig(lr=1e-3, warmup=20, decay_steps=max(args.steps, 100))

    params = api.init(cfg, jax.random.PRNGKey(0))
    opt_state = make_init_opt(cfg, policy, opt)(params)
    print(f"model: {cfg.name}, {api.n_params(cfg):,} params")

    cm = CheckpointManager(args.ckpt_dir, keep_last=2)
    start_step = 0
    if args.resume:
        meta, restored = cm.restore_latest({"params": params, "opt": opt_state})
        if meta is not None:
            params, opt_state = restored["params"], restored["opt"]
            start_step = meta["step"]
            print(f"resumed from step {start_step}")

    step_fn = jax.jit(make_train_step(cfg, policy, opt))
    pipe = SyntheticLM(cfg, shape, seed=0)
    pf = Prefetcher(pipe, start_step=start_step)
    ctl = ElasticController(["host0"], hosts_per_pod=1, chips_per_host=1,
                            model_axis=1, multi_pod=False)

    t_start = time.time()
    try:
        for i in range(start_step, start_step + args.steps):
            t0 = time.time()
            s, batch = pf.next()
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            params, opt_state, m = step_fn(params, opt_state, batch)
            dt = time.time() - t0
            ctl.on_step({"host0": dt})
            restart, plan, stragglers = ctl.check()
            if stragglers:
                print(f"  [straggler mitigation] slow hosts: {stragglers}")
            if i % 10 == 0:
                tok_s = args.batch * args.seq / dt
                print(f"step {i:4d} loss {float(m['loss']):.3f} "
                      f"{dt*1e3:6.0f} ms/step {tok_s:8.0f} tok/s")
            if (i + 1) % args.ckpt_every == 0:
                cm.save(i + 1, {"params": params, "opt": opt_state})
        cm.save(start_step + args.steps, {"params": params, "opt": opt_state})
        cm.wait()
        print(f"done: {args.steps} steps in {time.time()-t_start:.0f}s; "
              f"checkpoints in {args.ckpt_dir}")
    finally:
        pf.close()


if __name__ == "__main__":
    main()
