"""Multi-pod dry-run driver (deliverable e).

Lowers + compiles every (architecture x input shape) cell on the production
mesh — 16x16 single-pod and 2x16x16 multi-pod — and records
memory_analysis / cost_analysis / loop-corrected HLO counters / roofline
terms to benchmarks/results/dryrun/.

Run as a program, it adds 512 host devices to XLA_FLAGS before JAX
initializes its backends (the count is fixed then); importing the module
changes nothing.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-1.5b \
      --shape train_4k --mesh multi
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import json
import os
import sys
import traceback

from ..configs.base import (SHAPES, RunPolicy, default_preset, get_config,
                            list_archs)
from .. import hw
from ..core import counters
from . import compile_cache
from ..train.optimizer import OptConfig
from .mesh import make_production_mesh
from .steps import build_cell

RESULTS_DIR = os.path.join(os.path.dirname(__file__),
                           "..", "..", "..", "benchmarks", "results", "dryrun")


def default_policy(cfg, shape, **overrides) -> RunPolicy:
    """Paper-faithful baseline policy per cell."""
    base = dict(sharding_preset=default_preset(cfg))
    if shape.kind == "train":
        base.update(remat="full", n_microbatch=8)
    else:
        # inference: bf16 params, no remat
        base.update(remat="none", n_microbatch=1, params_f32=False)
    base.update(overrides)
    return RunPolicy(**base)


def cell_applicable(cfg, shape) -> tuple[bool, str]:
    if shape.name == "long_500k" and not cfg.subquadratic:
        return False, "skipped: full-attention arch at 524k decode " \
                      "(quadratic by construction; see DESIGN.md)"
    return True, ""


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             policy: RunPolicy | None = None, opt: OptConfig | None = None):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = cell_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": why}
    mesh = make_production_mesh(multi_pod=multi_pod)
    policy = policy or default_policy(cfg, shape)
    cell = build_cell(cfg, shape, policy, mesh, opt)
    m = counters.measure_cell(cell, hw.V5E)
    out = m.summary()
    out.update({"status": "ok", "mesh_kind": "multi" if multi_pod else "single"})
    return out


def main(argv=None):
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--preset", default=None)
    ap.add_argument("--remat", default=None)
    ap.add_argument("--microbatch", type=int, default=None)
    ap.add_argument("--compress", default=None)
    ap.add_argument("--out", default=RESULTS_DIR)
    args = ap.parse_args(argv)

    os.makedirs(args.out, exist_ok=True)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    cells = []
    if args.all:
        for arch in list_archs():
            for shape in SHAPES:
                cells.append((arch, shape))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        cells = [(args.arch, args.shape)]

    failures = 0
    for arch, shape_name in cells:
        for mp in meshes:
            tag = f"{arch}__{shape_name}__{'multi' if mp else 'single'}"
            path = os.path.join(args.out, tag + ".json")
            try:
                overrides = {}
                cfg = get_config(arch)
                shape = SHAPES[shape_name]
                if args.preset:
                    overrides["sharding_preset"] = args.preset
                if args.remat:
                    overrides["remat"] = args.remat
                if args.microbatch:
                    overrides["n_microbatch"] = args.microbatch
                if args.compress:
                    overrides["grad_compress"] = args.compress
                policy = default_policy(cfg, shape, **overrides)
                res = run_cell(arch, shape_name, mp, policy)
                with open(path, "w") as f:
                    json.dump(res, f, indent=1, default=str)
                if res["status"] == "ok":
                    r = res["roofline"]
                    print(f"[ok] {tag}: dominant={r['dominant']} "
                          f"bound={r['bound_s']*1e3:.2f}ms "
                          f"useful={r['useful_flops_ratio']:.3f} "
                          f"peak={res['memory']['peak_bytes']/2**30:.1f}GiB "
                          f"compile={res['compile_s']:.1f}s", flush=True)
                else:
                    print(f"[skip] {tag}: {res['reason']}", flush=True)
            except Exception as e:
                failures += 1
                print(f"[FAIL] {tag}: {e}", flush=True)
                traceback.print_exc()
                with open(path, "w") as f:
                    json.dump({"arch": arch, "shape": shape_name,
                               "status": "fail", "error": str(e)}, f)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = " ".join(filter(None, [
        os.environ.get("XLA_FLAGS"),
        "--xla_force_host_platform_device_count=512"]))
    main()
