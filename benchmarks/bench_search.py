import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=32")

"""Paper Fig.4 + Fig.5: search efficiency of random / BO / Collie(SA), and
the diagnostic-counter + MFS ablations — at bench scale (4x4 / 2x4x4 meshes,
reduced dims; see core/benchscale.py).

Phase 1 establishes ground truth: a long Collie campaign whose MFS catalog
defines the anomaly set.  Phase 2 runs each algorithm variant with a fixed
compile budget and fresh engine; an anomaly counts as found when the run
measures a point inside its ground-truth MFS with the anomaly firing —
exactly the paper's crediting.
"""
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core import anomaly
from repro.launch import compile_cache
from repro.core.benchscale import BENCH_SHAPES, bench_archs, bench_meshes
from repro.core.bo import bo_search
from repro.core.catalog import render_markdown, save_catalog
from repro.core.corpus import Corpus
from repro.core.engine import Engine
from repro.core.measure_cache import MeasureCache
from repro.core.random_search import random_search
from repro.core.sa import campaign, rank_counters, simulated_annealing
from repro.core.searchspace import SearchSpace

from common import RESULTS, credit_events, save_json, summarize_credits  # noqa: E402

ARCH_SUBSET = os.environ.get("ARCHS", "qwen2-1.5b,mixtral-8x7b,rwkv6-7b,recurrentgemma-2b").split(",")
GT_BUDGET = int(os.environ.get("GT_BUDGET", 200))
RUN_BUDGET = int(os.environ.get("RUN_BUDGET", 70))
SEEDS = (0,) if os.environ.get("RUN_BUDGET") else (0, 1)
N_WORKERS = int(os.environ.get("COLLIE_WORKERS", "8"))

# one persistent measurement cache shared by every engine in this run (and
# by repeat runs: a warm cache performs zero recompiles for known points).
# COLLIE_CACHE overrides the location; COLLIE_CACHE=0 disables.
_cache_env = os.environ.get("COLLIE_CACHE")
if _cache_env == "0":
    SHARED_CACHE = None
else:
    os.makedirs(RESULTS, exist_ok=True)
    SHARED_CACHE = MeasureCache(
        _cache_env or os.path.join(RESULTS, "measure_cache.sqlite"))

_STAT_KEYS = ("n_attempts", "n_compiles", "n_failures", "n_cache_hits",
              "n_disk_hits", "n_cache_misses", "compile_time")
_agg = {k: 0 for k in _STAT_KEYS}

DIAG = [("diag.collective_blowup", "max"), ("diag.memory_overshoot", "max"),
        ("diag.transpose_bytes", "max")]
PERF = [("perf.roofline_efficiency", "min"),
        ("perf.useful_flops_ratio", "min")]


def fresh(space):
    return Engine(space, bench_meshes(), n_workers=N_WORKERS,
                  persistent_cache=SHARED_CACHE if SHARED_CACHE is not None
                  else False)


def collect(engine):
    """Fold a finished engine's counters into the run aggregate (so the
    engine — and its cached Measurement objects — can be collected)."""
    s = engine.stats()
    for k in _STAT_KEYS:
        _agg[k] += s[k]


def aggregate_stats():
    agg = dict(_agg)
    hits = agg["n_cache_hits"] + agg["n_disk_hits"]
    agg["cache_hit_rate"] = hits / max(hits + agg["n_cache_misses"], 1)
    return agg


def main():
    compile_cache.enable()
    t0 = time.time()
    space = SearchSpace(bench_archs(ARCH_SUBSET), BENCH_SHAPES,
                    restrict={"grad_compress": ("none",),
                              "scan_layers": (True,)})
    # int8/bf16 compression points CHECK-crash this XLA build's
    # partitioner (see EXPERIMENTS.md) — excluded as untestable
    print(f"# search space size: {space.size():.3g}", flush=True)

    # ---- counter ranking (paper §7.2: sigma/mu over 10 probes)
    eng = fresh(space)
    ranked = rank_counters(eng, space,
                           [c for c, _ in DIAG] + [c for c, _ in PERF],
                           seed=123)
    collect(eng)
    print(f"# counter ranking: {ranked}", flush=True)
    diag_ranked = [(c, "max") for c in ranked if c.startswith("diag.")]
    perf_ranked = [(c, "min") for c in ranked if c.startswith("perf.")]

    # every find from every run below lands in one deduplicated corpus
    corpus = Corpus(meta={
        "scale": "bench", "archs": list(ARCH_SUBSET),
        "restrict": {"grad_compress": ["none"], "scan_layers": [True]},
        "source": "bench_search"})

    # ---- phase 1: ground truth
    gt_engine = fresh(space)
    gt = campaign(gt_engine, space, diag_ranked + perf_ranked, seed=7,
                  budget_compiles=GT_BUDGET, label="ground-truth",
                  corpus=corpus)
    save_catalog(gt.anomalies, os.path.join(os.path.dirname(__file__),
                                            "results", "bench_gt_catalog.json"),
                 {"budget": GT_BUDGET, "space": space.size()})
    collect(gt_engine)
    print(f"# ground truth: {len(gt.anomalies)} anomalies "
          f"({gt.n_attempts} attempts, {gt.wall_s:.0f}s)", flush=True)
    print(render_markdown(gt.anomalies, "Ground-truth anomalies (bench scale)"),
          flush=True)

    variants = {
        # random runs with mfs_construct=False (the paper's raw-fuzzing
        # baseline), so like the nomfs ablations below its "conditions" are
        # full witness points — not corpus-wired to avoid degenerate
        # one-off signatures
        "random": lambda e, s: random_search(e, space, seed=s,
                                             budget_compiles=RUN_BUDGET),
        "bo-diag": lambda e, s: bo_search(e, space, diag_ranked[0][0], "max",
                                          seed=s, budget_compiles=RUN_BUDGET,
                                          corpus=corpus),
        "collie-diag": lambda e, s: campaign(e, space, diag_ranked, seed=s,
                                             budget_compiles=RUN_BUDGET,
                                             label="collie-diag",
                                             corpus=corpus),
        "collie-perf": lambda e, s: campaign(e, space, perf_ranked, seed=s,
                                             budget_compiles=RUN_BUDGET,
                                             label="collie-perf",
                                             corpus=corpus),
        # nomfs ablations deliberately not corpus-wired: without MFS
        # construction their "conditions" are the full witness point, which
        # would flood the corpus with degenerate one-off signatures
        "sa-diag-nomfs": lambda e, s: campaign(e, space, diag_ranked, seed=s,
                                               budget_compiles=RUN_BUDGET,
                                               mfs_skip=False,
                                               mfs_construct=False,
                                               label="sa-diag-nomfs"),
        "sa-perf-nomfs": lambda e, s: campaign(e, space, perf_ranked, seed=s,
                                               budget_compiles=RUN_BUDGET,
                                               mfs_skip=False,
                                               mfs_construct=False,
                                               label="sa-perf-nomfs"),
    }
    summary = {}
    for name, fn in variants.items():
        credits = []
        for seed in SEEDS:
            e = fresh(space)
            r = fn(e, seed)
            collect(e)
            credits.append(credit_events(r.events, gt.anomalies))
        s = summarize_credits(credits, len(gt.anomalies))
        summary[name] = s
        means = [v["mean_compiles"] for v in s["per_gt"].values()
                 if v["mean_compiles"] is not None]
        mean_str = f"{sum(means)/len(means):.1f}" if means else "-"
        print(f"bench_search,{name},found={s['n_found']}/{s['n_gt']},"
              f"mean_compiles_to_find={mean_str}", flush=True)

    # raw (un-minimized) corpus of everything this run discovered — merge
    # into the committed corpus with `python -m repro.core.corpus merge`
    corpus.save(os.path.join(RESULTS, "bench_search_corpus.json"))
    print(f"# corpus: {len(corpus)} unique signatures "
          f"({sum(e.hits for e in corpus.ordered())} finds)", flush=True)

    engine_stats = aggregate_stats()
    save_json("bench_search.json", {
        "ground_truth_n": len(gt.anomalies),
        "budget": RUN_BUDGET, "seeds": list(SEEDS),
        "ranking": ranked,
        "summary": summary,
        "engine_stats": engine_stats,
        "wall_s": time.time() - t0,
    })
    print(f"# engine: {engine_stats['n_compiles']} compiles, "
          f"{engine_stats['n_failures']} failures, "
          f"hit_rate={engine_stats['cache_hit_rate']:.2f} "
          f"(disk {engine_stats['n_disk_hits']})", flush=True)
    print(f"# total {time.time()-t0:.0f}s", flush=True)


if __name__ == "__main__":
    main()
