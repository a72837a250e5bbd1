"""THE paper's tool, end to end: search a restricted workload space for
performance anomalies, print their Minimal Feature Sets, and give the
application-design advice of paper §7.3.

Mirrors the paper's RPC-library case study: a developer restricts the space
to what their application can generate (here: serving a dense GQA model),
Collie reports which regions of that space are anomalous and which condition
to break.

  XLA_FLAGS=--xla_force_host_platform_device_count=32 \
      PYTHONPATH=src python examples/collie_search.py --budget 60
"""
import os
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=32"

import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import argparse

from repro.core.benchscale import BENCH_SHAPES, bench_archs, bench_meshes
from repro.launch import compile_cache
from repro.core.catalog import render_markdown
from repro.core.engine import Engine
from repro.core.sa import campaign, rank_counters
from repro.core.searchspace import SearchSpace


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget", type=int, default=60)
    ap.add_argument("--restrict", action="store_true", default=True,
                    help="restrict to the 'serving a dense model' sub-space")
    args = ap.parse_args()

    restrict = {"arch": ("qwen2-1.5b", "tinyllama-1.1b"),
                "shape": ("prefill_s", "decode_s"),
                "grad_compress": ("none",)} if args.restrict else None
    space = SearchSpace(bench_archs(["qwen2-1.5b", "tinyllama-1.1b",
                                     "mixtral-8x7b"]),
                        BENCH_SHAPES, restrict=restrict)
    print(f"restricted search space: {space.size():.3g} points")
    eng = Engine(space, bench_meshes())

    counters = ["diag.collective_blowup", "diag.memory_overshoot",
                "perf.roofline_efficiency"]
    ranked = rank_counters(eng, space, counters, seed=5)
    order = [(c, "max" if c.startswith("diag.") else "min") for c in ranked]
    r = campaign(eng, space, order, seed=3, budget_compiles=args.budget)

    print(f"\n{len(r.anomalies)} anomalies in {r.n_attempts} attempts "
          f"({r.wall_s:.0f}s)\n")
    print(render_markdown(r.anomalies, "Anomalies in the restricted space"))

    print("\n-- design advice (paper §7.3 analogue) --")
    if not r.anomalies:
        print("no anomalies: any workload in this sub-space is safe "
              "(assuming the restriction captures the application).")
    for a in r.anomalies:
        breakable = [f"{f} (use any of "
                     f"{sorted(set(space.factors[f]) - set(v))})"
                     for f, v in a.conditions.items()
                     if f not in ("arch", "shape")
                     and set(v) != set(space.factors[f])]
        if breakable:
            print(f"* {a.describe()}\n    avoid by breaking: "
                  + "; or ".join(breakable[:3]))
        else:
            print(f"* {a.describe()}\n    intrinsic to this workload cell — "
                  "report to the platform team (vendor analogue)")


if __name__ == "__main__":
    main()
