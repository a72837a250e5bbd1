"""Batched serving example: continuous-batching engine over a small model.

  PYTHONPATH=src python examples/serve_lm.py --requests 12 --slots 4
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import argparse
import time

import jax
import numpy as np

from repro.configs.base import RunPolicy
from repro.launch import compile_cache
from repro.configs.all_archs import smoke_config
from repro.models import api
from repro.serve.engine import Request, ServingEngine


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.8)
    args = ap.parse_args()

    cfg = smoke_config(args.arch)
    policy = RunPolicy(remat="none", dtype="f32")
    params = api.init(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, policy, params, n_slots=args.slots,
                        cache_len=128, temperature=args.temperature)

    rng = np.random.default_rng(0)
    t0 = time.time()
    for i in range(args.requests):
        plen = int(rng.choice([8, 16]))
        eng.add_request(Request(rid=i,
                                prompt=rng.integers(0, cfg.vocab_size, plen,
                                                    dtype=np.int64).astype(np.int32),
                                max_new_tokens=args.max_new))
    done = eng.run()
    dt = time.time() - t0
    print(f"{len(done)} requests, {eng.stats['tokens_out']} tokens in "
          f"{dt:.1f}s ({eng.stats['tokens_out']/dt:.1f} tok/s); "
          f"{eng.stats['decode_steps']} batched decode steps, "
          f"{eng.stats['prefills']} prefills")
    for r in done[:4]:
        print(f"  rid={r.rid} len(prompt)={len(r.prompt)} out={r.out[:8]}...")


if __name__ == "__main__":
    main()
