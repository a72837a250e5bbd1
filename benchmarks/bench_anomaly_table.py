import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Paper Table 2 / Appendix A analogue: the PRODUCTION-scale anomaly catalog.

Runs the full Collie tool (ranked diagnostic+performance counters, SA + MFS)
over the real 10-arch x 4-shape space on the 16x16 and 2x16x16 production
meshes, and renders every found anomaly with its trigger conditions.
"""
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402

from repro.configs.base import SHAPES, get_config, list_archs
from repro.launch import compile_cache
from repro.core.catalog import render_markdown, save_catalog
from repro.core.engine import Engine
from repro.core.sa import campaign, rank_counters
from repro.core.searchspace import SearchSpace
from repro.launch.mesh import make_production_mesh

from common import save_json  # noqa: E402

BUDGET = int(os.environ.get("CATALOG_BUDGET", 140))

DIAG = [("diag.collective_blowup", "max"), ("diag.memory_overshoot", "max"),
        ("diag.transpose_bytes", "max")]
PERF = [("perf.roofline_efficiency", "min"),
        ("perf.useful_flops_ratio", "min")]


def main():
    compile_cache.enable()
    t0 = time.time()
    archs = {a: get_config(a) for a in list_archs()}
    space = SearchSpace(archs, dict(SHAPES),
                    restrict={"grad_compress": ("none",),
                              "scan_layers": (True,)})
    meshes = {"single": make_production_mesh(),
              "multi": make_production_mesh(multi_pod=True)}
    eng = Engine(space, meshes)
    ranked = rank_counters(eng, space,
                           [c for c, _ in DIAG] + [c for c, _ in PERF],
                           seed=42)
    order = ([(c, "max") for c in ranked if c.startswith("diag.")]
             + [(c, "min") for c in ranked if c.startswith("perf.")])
    r = campaign(eng, space, order, seed=21, budget_compiles=BUDGET,
                 label="collie-production")
    md = render_markdown(r.anomalies,
                         "Production-scale anomaly catalog (Table 2 analogue)")
    print(md, flush=True)
    save_catalog(r.anomalies,
                 os.path.join(os.path.dirname(__file__), "results",
                              "production_catalog.json"),
                 {"budget": BUDGET, "space_size": space.size(),
                  "compiles": r.n_attempts, "wall_s": r.wall_s})
    with open(os.path.join(os.path.dirname(__file__), "results",
                           "production_catalog.md"), "w") as f:
        f.write(md + "\n")
    print(f"bench_anomaly_table,collie,anomalies={len(r.anomalies)},"
          f"compiles={r.n_attempts},wall_s={r.wall_s:.0f}", flush=True)
    save_json("bench_anomaly_table.json",
              {"n_anomalies": len(r.anomalies), "compiles": r.n_attempts,
               "wall_s": time.time() - t0})


if __name__ == "__main__":
    main()
