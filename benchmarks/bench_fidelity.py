import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=32")

"""Multi-fidelity search efficiency (ISSUE 2 acceptance benchmark).

Phase 1 re-establishes ground truth at bench scale with a long full-fidelity
Collie campaign (regenerating ``results/bench_gt_catalog.json``) and commits
every (point, counters) measurement it made to
``results/bench_fidelity_pairs.json`` — the fixture the surrogate-quality
test (tests/test_surrogate.py) checks Spearman rank correlation against.

Phase 2 runs the SA campaign twice at the SAME attempt budget and fresh
engines: ``fidelity="full"`` (the PR-1 baseline) vs ``fidelity="prescreen"``
(surrogate prescreen + promotion).  An anomaly counts as found when the run
measures a point inside a ground-truth MFS with the anomaly firing — the
paper's Fig.4 crediting.  The headline metric is *full compiles per anomaly
found* (mean attempts at first find); the prescreened campaign must find at
least as many ground-truth anomaly kinds at >=2x fewer compiles per anomaly.

``results/bench_fidelity_baseline.json`` (committed; regenerate with
``python run.py --compare --update-baseline``) pins the prescreen metrics;
CI fails on >20% regression via ``python run.py --compare``.
"""
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core.benchscale import BENCH_SHAPES, bench_archs, bench_meshes
from repro.launch import compile_cache
from repro.core.catalog import render_markdown, save_catalog
from repro.core.corpus import Corpus
from repro.core.engine import Engine
from repro.core.measure_cache import MeasureCache
from repro.core.sa import campaign, rank_counters
from repro.core.searchspace import SearchSpace

from common import RESULTS, credit_events, save_json, summarize_credits  # noqa: E402

SMOKE = bool(os.environ.get("SMOKE"))
ARCH_SUBSET = os.environ.get(
    "ARCHS", "qwen2-1.5b,mixtral-8x7b" if SMOKE
    else "qwen2-1.5b,mixtral-8x7b,rwkv6-7b,recurrentgemma-2b").split(",")
GT_BUDGET = int(os.environ.get("GT_BUDGET", 30 if SMOKE else 160))
RUN_BUDGET = int(os.environ.get("RUN_BUDGET", 16 if SMOKE else 60))
SEEDS = tuple(int(s) for s in os.environ.get(
    "SEEDS", "0" if SMOKE else "0,1").split(","))
N_PROBES = int(os.environ.get("N_PROBES", 16 if SMOKE else 64))
OVERPROVISION = int(os.environ.get("OVERPROVISION", 4))
N_WORKERS = int(os.environ.get("COLLIE_WORKERS", "8"))

_cache_env = os.environ.get("COLLIE_CACHE")
if _cache_env == "0":
    SHARED_CACHE = None
else:
    os.makedirs(RESULTS, exist_ok=True)
    SHARED_CACHE = MeasureCache(
        _cache_env or os.path.join(RESULTS, "measure_cache.sqlite"))

DIAG = [("diag.collective_blowup", "max"), ("diag.memory_overshoot", "max")]
PERF = [("perf.roofline_efficiency", "min"),
        ("perf.useful_flops_ratio", "min")]

# SMOKE runs (CI's --compare gate) must never clobber the committed
# full-scale artifacts the tier-1 surrogate-quality tests read
_SUFFIX = "_smoke" if SMOKE else ""


def fresh(space):
    return Engine(space, bench_meshes(), n_workers=N_WORKERS,
                  persistent_cache=SHARED_CACHE if SHARED_CACHE is not None
                  else False)


def credited_kinds(events, gt):
    """Distinct ground-truth anomaly kinds this run's events credit."""
    kinds = set()
    for g in gt:
        if any(g.kind in e.kinds and g.matches(e.point) for e in events):
            kinds.add(g.kind)
    return kinds


def run_metrics(result, gt, engine_stats):
    credits = credit_events(result.events, gt)
    found = {i: c for i, c in credits.items() if c is not None}
    cpa = (sum(found.values()) / len(found)) if found else None
    return {
        "n_gt": len(gt),
        "n_found": len(found),
        "kinds_found": sorted(credited_kinds(result.events, gt)),
        "compiles_per_anomaly": cpa,
        "n_attempts": result.n_attempts,
        "n_compiles": engine_stats.get("n_compiles"),
        "n_screened_out": engine_stats.get("n_screened_out"),
        "n_promoted": engine_stats.get("n_promoted"),
        "n_struct_hits": engine_stats.get("n_struct_hits"),
        "n_lowerings": engine_stats.get("n_lowerings"),
        "credits": {str(i): c for i, c in credits.items()},
    }


def main():
    compile_cache.enable()
    t0 = time.time()
    restrict = {"grad_compress": ("none",), "scan_layers": (True,)}
    if SMOKE:
        # large unrolled-microbatch train cells compile for minutes on CI
        # runners — cap the unroll while keeping the pathology reachable
        restrict["n_microbatch"] = (1, 2, 4, 8)
    space = SearchSpace(bench_archs(ARCH_SUBSET), BENCH_SHAPES,
                        restrict=restrict)
    print(f"# search space size: {space.size():.3g}", flush=True)

    # ---- phase 1: ground truth (full fidelity) + measurement fixture
    gt_engine = fresh(space)
    # a diverse random-probe backbone for the committed fixture: campaign
    # points cluster tightly around witnesses (MFS probes vary one factor at
    # a time), which alone would make rank-correlation estimates degenerate
    import random as _random
    probe_rng = _random.Random(42)
    probes = [space.random_point(probe_rng) for _ in range(N_PROBES)]
    gt_engine.measure_batch(probes, prescreen=0)   # fixture is full-fidelity
    ranked = rank_counters(gt_engine, space,
                           [c for c, _ in DIAG] + [c for c, _ in PERF],
                           seed=123)
    counters_cfg = [(c, "max" if c.startswith("diag.") else "min")
                    for c in ranked]
    corpus = Corpus(meta={
        "scale": "bench", "archs": list(ARCH_SUBSET),
        "restrict": {k: list(v) for k, v in restrict.items()},
        "source": "bench_fidelity"})
    gt = campaign(gt_engine, space, counters_cfg, seed=7,
                  budget_compiles=GT_BUDGET, label="ground-truth",
                  corpus=corpus)
    save_catalog(gt.anomalies,
                 os.path.join(RESULTS, f"bench_gt_catalog{_SUFFIX}.json"),
                 {"budget": GT_BUDGET, "space": space.size(),
                  "archs": ARCH_SUBSET})
    # every measurement phase 1 completed, as (point, counters) pairs — the
    # committed surrogate-quality fixture (predictions need no devices)
    pairs = [[dict(k), dict(v)] for k, v in gt_engine.cache.items()
             if v is not None]
    save_json(f"bench_fidelity_pairs{_SUFFIX}.json", {
        "archs": ARCH_SUBSET,
        "restrict": {k: list(v) for k, v in restrict.items()},
        "mesh_shapes": {"single": {"data": 4, "model": 4},
                        "multi": {"pod": 2, "data": 4, "model": 4}},
        "pairs": pairs,
    })
    gt_stats = gt_engine.stats()
    gt_engine.close()
    print(f"# ground truth: {len(gt.anomalies)} anomalies, "
          f"{len(pairs)} measured points ({gt.n_attempts} attempts, "
          f"{gt.wall_s:.0f}s)", flush=True)
    print(render_markdown(gt.anomalies, "Ground-truth anomalies (bench scale)"),
          flush=True)

    # ---- phase 2: equal-budget full vs prescreen SA campaigns
    summary = {}
    for fid in ("full", "prescreen"):
        per_seed = []
        for seed in SEEDS:
            e = fresh(space)
            r = campaign(e, space, counters_cfg, seed=seed,
                         budget_compiles=RUN_BUDGET, label=f"sa-{fid}",
                         fidelity=fid, overprovision=OVERPROVISION,
                         corpus=corpus)
            per_seed.append(run_metrics(r, gt.anomalies, e.stats()))
            e.close()
        agg = summarize_credits(
            [{int(i): c for i, c in m["credits"].items()} for m in per_seed],
            len(gt.anomalies))
        kinds = sorted(set().union(*[set(m["kinds_found"])
                                     for m in per_seed]))
        cpas = [m["compiles_per_anomaly"] for m in per_seed
                if m["compiles_per_anomaly"] is not None]
        # informational (ISSUE 5): how much of the run's realized work was
        # served by structural dedup instead of an XLA compile
        struct_hits = sum(m.get("n_struct_hits") or 0 for m in per_seed)
        compiles = sum(m.get("n_compiles") or 0 for m in per_seed)
        summary[fid] = {
            "per_seed": per_seed,
            "n_found": agg["n_found"], "n_gt": agg["n_gt"],
            "kinds_found": kinds,
            "compiles_per_anomaly":
                (sum(cpas) / len(cpas)) if cpas else None,
            "n_struct_hits": struct_hits,
            "struct_hit_rate":
                struct_hits / max(struct_hits + compiles, 1),
        }
        print(f"bench_fidelity,{fid},found={agg['n_found']}/{agg['n_gt']},"
              f"kinds={'+'.join(kinds) or '-'},"
              f"compiles_per_anomaly="
              f"{summary[fid]['compiles_per_anomaly'] or float('nan'):.1f}",
              flush=True)

    corpus.save(os.path.join(RESULTS, f"bench_fidelity_corpus{_SUFFIX}.json"))
    print(f"# corpus: {len(corpus)} unique signatures "
          f"({sum(e.hits for e in corpus.ordered())} finds)", flush=True)

    full_cpa = summary["full"]["compiles_per_anomaly"]
    pre_cpa = summary["prescreen"]["compiles_per_anomaly"]
    speedup = (full_cpa / pre_cpa) if (full_cpa and pre_cpa) else None
    # no-evidence runs (either variant credited nothing) must not pass
    ok = (speedup is not None and speedup >= 2.0
          and set(summary["full"]["kinds_found"])
          <= set(summary["prescreen"]["kinds_found"]))
    save_json(f"bench_fidelity{_SUFFIX}.json", {
        "budget": RUN_BUDGET, "gt_budget": GT_BUDGET,
        "seeds": list(SEEDS), "archs": ARCH_SUBSET,
        "overprovision": OVERPROVISION,
        "ground_truth_n": len(gt.anomalies),
        "summary": {f: {k: v for k, v in s.items() if k != "per_seed"}
                    for f, s in summary.items()},
        "per_seed": {f: s["per_seed"] for f, s in summary.items()},
        "compile_speedup_per_anomaly": speedup,
        "acceptance_ok": ok,
        "gt_stats": {k: gt_stats[k] for k in
                     ("n_compiles", "n_disk_hits", "compile_time",
                      "n_struct_hits", "n_lowerings", "lower_time")},
        "wall_s": time.time() - t0,
    })
    print(f"# prescreen vs full: {speedup and f'{speedup:.1f}x' or 'n/a'} "
          f"fewer compiles per anomaly "
          f"({'OK' if ok else 'BELOW TARGET'})", flush=True)
    print(f"# total {time.time()-t0:.0f}s", flush=True)


if __name__ == "__main__":
    main()
