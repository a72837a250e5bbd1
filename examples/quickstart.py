"""Quickstart: train a tiny qwen2-family model on synthetic data (CPU, ~1min),
then serve a few batched requests from the trained weights.

  PYTHONPATH=src python examples/quickstart.py
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import RunPolicy, ShapeSpec
from repro.launch import compile_cache
from repro.configs.all_archs import smoke_config
from repro.data.pipeline import SyntheticLM
from repro.models import api
from repro.serve.engine import Request, ServingEngine
from repro.train.optimizer import OptConfig
from repro.train.train_step import make_init_opt, make_train_step


def main():
    compile_cache.enable()
    cfg = smoke_config("qwen2-1.5b")
    shape = ShapeSpec("quick", "train", 64, 8)
    policy = RunPolicy(remat="none", dtype="f32", n_microbatch=2)
    opt = OptConfig(lr=3e-3, warmup=5, decay_steps=300)

    params = api.init(cfg, jax.random.PRNGKey(0))
    print(f"model: {cfg.name}, {api.n_params(cfg):,} params")
    opt_state = make_init_opt(cfg, policy, opt)(params)
    step = jax.jit(make_train_step(cfg, policy, opt))
    pipe = SyntheticLM(cfg, shape, seed=0)

    for i in range(60):
        batch = {k: jnp.asarray(v) for k, v in pipe.batch(i).items()}
        params, opt_state, m = step(params, opt_state, batch)
        if i % 10 == 0:
            print(f"step {i:3d} loss {float(m['loss']):.3f} "
                  f"lr {float(m['lr']):.2e} |grad| {float(m['grad_norm']):.2f}")

    print("\nserving 4 batched requests from the trained model:")
    eng = ServingEngine(cfg, RunPolicy(remat='none', dtype='f32'), params,
                        n_slots=2, cache_len=64)
    for i in range(4):
        eng.add_request(Request(rid=i, prompt=np.arange(6, dtype=np.int32) + i,
                                max_new_tokens=8))
    for r in eng.run():
        print(f"  request {r.rid}: {list(r.prompt)} -> {r.out}")
    print("stats:", eng.stats)


if __name__ == "__main__":
    main()
