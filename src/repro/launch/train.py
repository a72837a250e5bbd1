"""Production training launcher.

Composes: mesh construction, sharded param/opt-state init, logical-axis
shardings, microbatched train step, host-sharded data pipeline with
prefetch, atomic async checkpointing with resume, heartbeat/straggler/
elastic hooks.  The step, its shardings and its donation are the measured
cell's (``launch/steps.build_cell``); parameters and optimizer state are
created under ``jit`` straight into their shardings, so no device ever
holds more than its share.  On a real fleet the same entrypoint runs per
host with ``jax.distributed.initialize`` and the production mesh.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --smoke \
      --steps 50
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time

import jax

from ..configs.base import RunPolicy, ShapeSpec, get_config
from ..ckpt.checkpoint import CheckpointManager
from ..data.pipeline import Prefetcher, SyntheticLM
from ..models import api
from ..runtime.elastic import ElasticController
from ..train.optimizer import OptConfig
from ..train.train_step import make_init_opt
from . import compile_cache
from .mesh import make_host_mesh, make_production_mesh
from .steps import build_cell
from .sharding import use_rules


def main(argv=None) -> dict:
    """Train; returns {"losses", "step_s", "bytes_in_use"} (one entry per
    step, and per device where the backend reports memory)."""
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the model to this many layers (widths stay)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--preset", default="fsdp")
    ap.add_argument("--remat", default="dots")
    ap.add_argument("--microbatch", type=int, default=2)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--compress", default="none")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--model-axis", type=int, default=1,
                    help="size of the host mesh's 'model' axis")
    ap.add_argument("--ckpt-dir",
                    default=os.path.join(compile_cache.ROOT, "runs", "train",
                                         "ckpt"))
    ap.add_argument("--ckpt-every", type=int, default=25,
                    help="steps between checkpoints; 0 writes none")
    ap.add_argument("--resume", action="store_true",
                    help="continue from the newest checkpoint in --ckpt-dir")
    ap.add_argument("--production-mesh", action="store_true",
                    help="use the 16x16 production mesh (needs 256 devices)")
    args = ap.parse_args(argv)

    if args.smoke:
        from ..configs.all_archs import smoke_config
        cfg = smoke_config(args.arch)
    else:
        cfg = get_config(args.arch)
    if args.layers is not None:
        cfg = dataclasses.replace(cfg, name=f"{cfg.name}-{args.layers}l",
                                  n_layers=args.layers)
    shape = ShapeSpec("train", "train", args.seq, args.batch)
    policy = RunPolicy(sharding_preset=args.preset, remat=args.remat,
                       n_microbatch=args.microbatch,
                       optimizer=args.optimizer, grad_compress=args.compress)
    opt = OptConfig(name=args.optimizer, lr=args.lr, warmup=10,
                    decay_steps=max(args.steps, 100))
    mesh = (make_production_mesh() if args.production_mesh
            else make_host_mesh(args.model_axis))
    cell = build_cell(cfg, shape, policy, mesh, opt)
    pshard, oshard, bshard = cell.in_shardings

    with mesh, use_rules(mesh, cell.rules):
        params = jax.jit(lambda key: api.init(cfg, key),
                         out_shardings=pshard)(jax.random.PRNGKey(args.seed))
        opt_state = jax.jit(make_init_opt(cfg, policy, opt, mesh),
                            out_shardings=oshard)(params)
        step_fn = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                          out_shardings=cell.out_shardings,
                          donate_argnums=cell.donate_argnums)

        cm = CheckpointManager(args.ckpt_dir, keep_last=2)
        start = 0
        if args.resume:
            meta, restored = cm.restore_latest(
                {"params": params, "opt": opt_state},
                {"params": pshard, "opt": oshard})
            if meta is not None:
                params, opt_state = restored["params"], restored["opt"]
                start = meta["step"]
                print(f"[launch] resumed from step {start}")

        n_hosts = jax.process_count()
        pipe = SyntheticLM(cfg, shape, seed=args.seed,
                           host_index=jax.process_index(), n_hosts=n_hosts)
        pf = Prefetcher(pipe, start_step=start)
        ctl = ElasticController([f"host{i}" for i in range(n_hosts)],
                                hosts_per_pod=max(n_hosts, 1),
                                chips_per_host=len(jax.local_devices()),
                                model_axis=mesh.shape.get("model", 1),
                                multi_pod="pod" in mesh.shape)
        print(f"[launch] {cfg.name}: {api.n_params(cfg):,} params on "
              f"{dict(mesh.shape)}; policy={args.preset}/{args.remat}/"
              f"mb{args.microbatch}/{policy.dtype}", flush=True)
        out = {"losses": [], "step_s": [], "bytes_in_use": []}
        try:
            for i in range(start, start + args.steps):
                t0 = time.perf_counter()
                _, batch = pf.next()
                batch = {k: jax.device_put(v, bshard[k])
                         for k, v in batch.items()}
                params, opt_state, m = step_fn(params, opt_state, batch)
                jax.block_until_ready((params, opt_state, m))
                dt = time.perf_counter() - t0
                ctl.on_step({f"host{jax.process_index()}": dt})
                out["losses"].append(float(m["loss"]))
                out["step_s"].append(dt)
                if i % 10 == 0:
                    print(f"step {i:5d} loss {out['losses'][-1]:.4f} "
                          f"{dt*1e3:7.0f} ms", flush=True)
                if args.ckpt_every and ((i + 1) % args.ckpt_every == 0
                                        or i + 1 == start + args.steps):
                    cm.save(i + 1, {"params": params, "opt": opt_state})
            out["bytes_in_use"] = [
                (d.memory_stats() or {}).get("bytes_in_use")
                for d in mesh.devices.flat]
            cm.wait()
        finally:
            pf.close()
    print("[launch] done")
    return out


if __name__ == "__main__":
    main()
