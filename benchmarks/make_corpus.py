import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=32")

"""Regenerate the committed anomaly regression corpus (ISSUE 4).

Reads the committed ground-truth catalog (``results/bench_gt_catalog.json``,
produced by bench_fidelity.py's full-scale phase 1) and converts every MFS
into a deduplicated, *minimized* corpus entry:

  1. ddmin the witness toward the canonical baseline point while the
     anomaly kind stays triggered (core/minimize.py) — real full-fidelity
     measurements, batched;
  2. tighten the single-factor MFS conditions with pairwise probes;
  3. harvest the minimizer's near-miss probes (one kept-factor away from the
     minimized witness, verified NOT to trigger) as replay control points;
  4. fold into the corpus under the anomaly's signature (kind + UNCOUPLED
     condition projection) — re-discoveries merge instead of duplicating.

Output: ``results/anomaly_corpus.json`` — the committed corpus that
``tests/test_corpus_regression.py`` replays in CI.  Uses the shared
persistent measurement cache, so regeneration after an intended behaviour
change is cheap for unchanged points.
"""
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core import anomaly as anomaly_mod
from repro.launch import compile_cache
from repro.core.catalog import load_catalog
from repro.core.corpus import Corpus, CorpusEntry, signature
from repro.core.engine import Engine
from repro.core.measure_cache import MeasureCache
from repro.core.minimize import boundary_controls, minimize_witness, \
    tighten_conditions
from repro.core.mfs import MFS
from repro.core.benchscale import BENCH_SHAPES, bench_archs, bench_meshes
from repro.core.searchspace import SearchSpace

from common import RESULTS, save_json  # noqa: E402

CATALOG = os.environ.get(
    "CATALOG", os.path.join(RESULTS, "bench_gt_catalog.json"))
OUT = os.environ.get("OUT", os.path.join(RESULTS, "anomaly_corpus.json"))
N_WORKERS = int(os.environ.get("COLLIE_WORKERS", "8"))
MAX_PROBES = int(os.environ.get("MAX_PROBES", 64))
TIGHTEN_PROBES = int(os.environ.get("TIGHTEN_PROBES", 16))
MAX_CONTROLS = int(os.environ.get("MAX_CONTROLS", 2))

# must match the space the GT campaign searched (bench_fidelity.py full run)
RESTRICT = {"grad_compress": ("none",), "scan_layers": (True,)}

_cache_env = os.environ.get("COLLIE_CACHE")
if _cache_env == "0":
    SHARED_CACHE = False
else:
    os.makedirs(RESULTS, exist_ok=True)
    SHARED_CACHE = MeasureCache(
        _cache_env or os.path.join(RESULTS, "measure_cache.sqlite"))


def main():
    compile_cache.enable()
    t0 = time.time()
    import json
    with open(CATALOG) as f:
        cat_meta = json.load(f).get("meta", {})
    archs = cat_meta.get("archs") or \
        "qwen2-1.5b,mixtral-8x7b,rwkv6-7b,recurrentgemma-2b".split(",")
    space = SearchSpace(bench_archs(archs), BENCH_SHAPES, restrict=RESTRICT)
    engine = Engine(space, bench_meshes(), n_workers=N_WORKERS,
                    persistent_cache=SHARED_CACHE)
    corpus = Corpus(meta={
        "scale": "bench",
        "archs": list(archs),
        "restrict": {k: list(v) for k, v in RESTRICT.items()},
        "catalog": os.path.basename(CATALOG),
        "gt_budget": cat_meta.get("budget"),
    })
    for mfs in load_catalog(CATALOG):
        sig = signature(mfs.kind, mfs.conditions)
        # one witness probe up front: a stale entry must not burn the
        # tighten/minimize budget (the engine cache makes the re-measure
        # inside minimize_witness free)
        w = space.normalize(mfs.witness)
        m = engine.measure(w)
        if m is None or mfs.kind not in anomaly_mod.kinds(
                m, w.get("remat", "none")):
            print(f"corpus,SKIP-UNTRIGGERED,{sig}", flush=True)
            continue
        tight = tighten_conditions(
            engine, space,
            MFS(mfs.kind, mfs.conditions, mfs.witness, mfs.counters),
            max_probes=TIGHTEN_PROBES)
        # minimize inside the tightened conditions, so the committed witness
        # still exemplifies the catalog entry it came from
        mr = minimize_witness(engine, space, mfs.witness, mfs.kind,
                              max_probes=MAX_PROBES, within=tight)
        if not mr.triggered:
            print(f"corpus,SKIP-UNTRIGGERED,{sig}", flush=True)
            continue
        n_tighten = tight.n_tests        # tighten() started from n_tests=0
        # counters must describe the committed witness, not the raw point it
        # was minimized from (cache hit: ddmin measured the accepted point)
        m_min = engine.measure(mr.point)
        controls = boundary_controls(engine, space, mr.point, mfs.kind,
                                     tight.conditions,
                                     max_controls=MAX_CONTROLS)
        for nm in mr.near_misses:        # free extra controls from ddmin
            if len(controls) >= MAX_CONTROLS:
                break
            if nm not in controls:
                controls.append(nm)
        entry = CorpusEntry(
            signature=sig, kind=mfs.kind,
            conditions={k: tuple(v) for k, v in
                        sorted(tight.conditions.items())},
            witness=mr.point, raw_witness=space.normalize(mfs.witness),
            distance=mr.distance, raw_distance=mr.raw_distance,
            minimized=True,
            sources=["gt-catalog"],
            controls=controls,
            counters=m_min,
            n_probes=mr.n_probes + n_tighten + len(controls))
        folded = corpus.add_entry(entry)
        print(f"corpus,{'merged' if folded is not entry else 'new'},{sig},"
              f"distance={mr.raw_distance}->{mr.distance},"
              f"probes={entry.n_probes},controls={len(entry.controls)}",
              flush=True)
    corpus.save(OUT)
    s = engine.stats()
    engine.close()
    save_json("make_corpus_stats.json", {
        "entries": len(corpus), "catalog": CATALOG,
        "engine": {k: s[k] for k in ("n_attempts", "n_compiles", "n_failures",
                                     "n_disk_hits", "n_minimize_probes",
                                     "compile_time")},
        "wall_s": time.time() - t0,
    })
    print(f"# corpus: {len(corpus)} entries -> {OUT} "
          f"({s['n_compiles']} compiles, {s['n_disk_hits']} disk hits, "
          f"{time.time() - t0:.0f}s)", flush=True)


if __name__ == "__main__":
    main()
