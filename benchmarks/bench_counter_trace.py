import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=32")

"""Paper Fig.6: diagnostic counter values during the search, with anomaly
marks, for Collie vs Collie-without-MFS vs random."""
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core.benchscale import BENCH_SHAPES, bench_archs, bench_meshes
from repro.launch import compile_cache
from repro.core.engine import Engine
from repro.core.random_search import random_search
from repro.core.sa import simulated_annealing
from repro.core.searchspace import SearchSpace

from common import save_json  # noqa: E402

COUNTER = "diag.collective_blowup"
BUDGET = int(os.environ.get("TRACE_BUDGET", 60))


def trace(result):
    out = []
    for e in result.events:
        out.append({"n": e.n_spent, "t": e.t,
                    "value": e.counter_value,
                    "anomaly": sorted(e.kinds) if e.kinds else [],
                    "new_mfs": e.new_mfs.describe() if e.new_mfs else None})
    return out


def main():
    compile_cache.enable()
    t0 = time.time()
    space = SearchSpace(bench_archs(["qwen2-1.5b", "mixtral-8x7b"]),
                        BENCH_SHAPES,
                        restrict={"grad_compress": ("none",),
                              "scan_layers": (True,)})
    runs = {}
    for name, kw in [
            ("collie", dict(mfs_skip=True, mfs_construct=True)),
            ("sa-nomfs", dict(mfs_skip=False, mfs_construct=False))]:
        eng = Engine(space, bench_meshes())
        r = simulated_annealing(eng, space, COUNTER, "max", seed=11,
                                budget_compiles=BUDGET, **kw)
        runs[name] = {"trace": trace(r), "anomalies": len(r.anomalies)}
        print(f"bench_counter_trace,{name},anomalies={len(r.anomalies)},"
              f"compiles={r.n_attempts}", flush=True)
    eng = Engine(space, bench_meshes())
    r = random_search(eng, space, seed=11, budget_compiles=BUDGET)
    runs["random"] = {"trace": trace(r),
                      "anomalies": len({(a.kind, tuple(sorted(a.witness.items())))
                                        for a in r.anomalies})}
    print(f"bench_counter_trace,random,compiles={r.n_attempts}", flush=True)
    vals = [e["value"] for run in runs.values() for e in run["trace"]
            if e["value"] is not None]
    vmax = max(vals) if vals else 1.0
    save_json("bench_counter_trace.json",
              {"counter": COUNTER, "normalizer": vmax, "runs": runs,
               "wall_s": time.time() - t0})
    print(f"# total {time.time()-t0:.0f}s", flush=True)


if __name__ == "__main__":
    main()
