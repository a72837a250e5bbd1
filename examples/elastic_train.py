"""Fault-tolerance demo: training with simulated host failures — heartbeat
detection, elastic re-mesh planning, checkpoint restart, straggler flags.

  PYTHONPATH=src python examples/elastic_train.py
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import tempfile

import jax
import jax.numpy as jnp

from repro.configs.base import RunPolicy, ShapeSpec
from repro.launch import compile_cache
from repro.configs.all_archs import smoke_config
from repro.ckpt.checkpoint import CheckpointManager
from repro.data.pipeline import SyntheticLM
from repro.models import api
from repro.runtime.elastic import ElasticController
from repro.train.optimizer import OptConfig
from repro.train.train_step import make_init_opt, make_train_step


class SimClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def main():
    compile_cache.enable()
    cfg = smoke_config("tinyllama-1.1b")
    shape = ShapeSpec("el", "train", 64, 8)
    policy = RunPolicy(remat="none", dtype="f32")
    opt = OptConfig(lr=1e-3, warmup=5, decay_steps=100)
    ckpt_dir = tempfile.mkdtemp(prefix="elastic_")

    hosts = [f"host{i}" for i in range(8)]
    clock = SimClock()
    ctl = ElasticController(hosts, hosts_per_pod=4, chips_per_host=4,
                            model_axis=4, multi_pod=True,
                            heartbeat_timeout_s=5, clock=clock)

    params = api.init(cfg, jax.random.PRNGKey(0))
    st = make_init_opt(cfg, policy, opt)(params)
    step_fn = jax.jit(make_train_step(cfg, policy, opt))
    pipe = SyntheticLM(cfg, shape, seed=0)
    cm = CheckpointManager(ckpt_dir, async_write=False)

    failed_at = 12
    i = 0
    while i < 25:
        clock.t += 1.0
        batch = {k: jnp.asarray(v) for k, v in pipe.batch(i).items()}
        params, st, m = step_fn(params, st, batch)
        # all hosts beat except host7 after the simulated failure
        times = {h: 1.0 for h in hosts if not (h == "host7" and i >= failed_at)}
        times["host3"] = 1.8 if i % 3 == 0 else 1.0   # intermittent straggler
        ctl.on_step(times)
        if i % 5 == 0:
            cm.save(i, {"params": params, "opt": st})
            print(f"step {i:3d} loss {float(m['loss']):.3f} [checkpoint]")
        restart, plan, stragglers = ctl.check()
        if stragglers:
            print(f"step {i:3d} stragglers flagged: {stragglers}")
        if restart:
            print(f"step {i:3d} HOST FAILURE detected: {plan.dropped_hosts} "
                  f"-> new mesh {dict(zip(plan.axis_names, plan.mesh_shape))}"
                  f" ({plan.note})")
            meta, restored = cm.restore_latest({"params": params, "opt": st})
            params, st = restored["params"], restored["opt"]
            i = meta["step"]
            print(f"         resumed from checkpoint step {i}")
            # (on a real fleet: rebuild jit with the plan's mesh + shardings)
        i += 1
    print("survived the failure; final loss",
          float(m["loss"]))


if __name__ == "__main__":
    main()
