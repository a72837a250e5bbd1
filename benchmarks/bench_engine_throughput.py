import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=32")

"""Engine measurement throughput: serial cold baseline vs the batched,
persistently-cached engine on a campaign-shaped request stream.

The stream mirrors how bench_search.py actually loads the engine: several
phases (counter ranking, ground truth, per-variant runs), each served by a
FRESH engine, drawing overlapping point sets from a common pool — plus a
final phase that replays the first exactly (a repeated benchmark run).
The baseline measures each phase serially with per-engine memory caches only
(the pre-ISSUE-1 engine); the optimized path shares one on-disk measurement
cache across phases and measures each phase as a concurrent batch.

Emits points/sec for both, the speedup, and the cache hit rate, as JSON —
future PRs track the regression.  Env knobs: SMOKE=1 shrinks everything for
CI; COLLIE_WORKERS sets the optimized batch width (default 8).

Split-phase structural dedup (ISSUE 5 acceptance): a second,
campaign-probe-shaped stream — per witness, the three probe shapes the
corpus lifecycle actually submits (construct_mfs one-factor flips,
minimize_witness ddmin keep-set candidates, tighten_conditions pairwise
flips), every point unique and budget-charged — is measured twice, fresh
engine per probe batch sharing one scratch persistent cache per variant:
struct_dedup=False (every unique point compiles) vs struct_dedup=True
(points lowering to a known fingerprint skip XLA, within and across
batches).  Headline metrics are compiles avoided / structural hit rate /
compile-time saved (NOT wall-clock: this box is 2-core); acceptance is
>= 20% of unique promoted points served without a compile, with
byte-identical counters.
"""
import json
import random
import shutil
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core.benchscale import BENCH_SHAPES, bench_archs, bench_meshes
from repro.launch import compile_cache
from repro.core.engine import Engine
from repro.core.measure_cache import MeasureCache
from repro.core.searchspace import SearchSpace

from common import RESULTS, save_json  # noqa: E402

SMOKE = bool(int(os.environ.get("SMOKE", "0")))
N_WORKERS = int(os.environ.get("COLLIE_WORKERS", "8"))
POOL = 6 if SMOKE else 24          # unique points available
PHASE = 4 if SMOKE else 16         # points requested per phase
# distinct campaign phases, each a fresh engine — matching bench_search.py
# at default budgets: ranking + ground truth + 6 variants x 2 seeds = 14
# engines (the final phase here is an exact repeat run)
N_PHASES = 2 if SMOKE else 13
N_WITNESSES = 1 if SMOKE else 3    # struct-dedup stream: MFS-probe batches


def sample_pool(space, n, seed=0):
    rng = random.Random(seed)
    pts, seen = [], set()
    while len(pts) < n:
        p = space.random_point(rng)
        k = space.point_key(p)
        if k not in seen:
            seen.add(k)
            pts.append(p)
    return pts


def make_stream(pool, seed=1):
    """Per-phase request lists: overlapping draws + an exact repeat run."""
    rng = random.Random(seed)
    phases = [pool[:PHASE]]                        # phase 1: first visit
    for _ in range(N_PHASES - 1):
        phases.append([pool[rng.randrange(len(pool))] for _ in range(PHASE)])
    phases.append(list(phases[0]))                 # repeated benchmark run
    return phases


def run_serial(space, meshes, phases):
    """Pre-ISSUE-1 behavior: fresh engine per phase, serial, memory cache."""
    t0 = time.time()
    compiles = 0
    for phase in phases:
        eng = Engine(space, meshes, n_workers=1, persistent_cache=False)
        for p in phase:
            eng.measure(p)
        compiles += eng.n_compiles + eng.n_failures
    return time.time() - t0, compiles


def run_optimized(space, meshes, phases, cache_path):
    """Fresh engine per phase sharing one persistent cache, batched."""
    cache = MeasureCache(cache_path)
    t0 = time.time()
    compiles = 0
    hits = misses = 0
    for phase in phases:
        eng = Engine(space, meshes, n_workers=N_WORKERS,
                     persistent_cache=cache)
        # raw full-fidelity throughput: a COLLIE_PRESCREEN default would
        # skip compiles and corrupt the points/sec metric
        eng.measure_batch(phase, prescreen=0)
        s = eng.stats()
        compiles += s["n_compiles"] + s["n_failures"]
        hits += s["n_cache_hits"] + s["n_disk_hits"]
        misses += s["n_cache_misses"]
    cache.close()
    return time.time() - t0, compiles, hits / max(hits + misses, 1)


def campaign_probe_batches(space, n_witnesses, seed=3):
    """Per witness, the three probe streams the corpus lifecycle submits:

    * construct_mfs — the witness + all its valid one-factor flips;
    * minimize_witness — ddmin keep-set candidates walked toward the
      canonical baseline (chunks, complements, greedy singles);
    * tighten_conditions — pairwise flips over the uncoupled factors.

    Every point is globally unique (deduplicated by key), so each would be
    charged and compiled by a fingerprint-less engine.
    """
    from repro.core.minimize import WORKLOAD_FACTORS, baseline_point
    from repro.core.searchspace import UNCOUPLED

    rng = random.Random(seed)
    batches = []
    seen: set = set()

    def add(batch, p):
        if not space.valid(p):
            return
        k = space.point_key(p)
        if k not in seen:
            seen.add(k)
            batch.append(p)

    for _ in range(n_witnesses):
        w = space.random_point(rng)
        mfs_b: list = []
        add(mfs_b, w)
        for f, dom in space.factors.items():
            for v in dom:
                add(mfs_b, space.normalize({**w, f: v}))
        base = baseline_point(space, w["arch"], w["shape"])
        K = [f for f in sorted(space.factors)
             if f not in WORKLOAD_FACTORS and w[f] != base[f]]
        dd_b: list = []
        add(dd_b, base)
        step = max(len(K) // 2, 1)
        chunks = [K[i:i + step] for i in range(0, len(K), step)][:2]
        for c in chunks + [[f for f in K if f not in c] for c in chunks]:
            p = dict(base)
            p.update({f: w[f] for f in c})
            add(dd_b, space.normalize(p))
        for f in K:
            p = dict(base)
            p.update({g: w[g] for g in K if g != f})
            add(dd_b, space.normalize(p))
            add(dd_b, space.normalize({**base, f: w[f]}))
        ti_b: list = []
        fs = [f for f in UNCOUPLED
              if f in space.factors and len(space.factors[f]) > 1]
        pairs = [(f, v, g, u) for i, f in enumerate(fs) for g in fs[i + 1:]
                 for v in space.factors[f] if v != w.get(f)
                 for u in space.factors[g] if u != w.get(g)][:12]
        for f, v, g, u in pairs:
            add(ti_b, space.normalize({**w, f: v, g: u}))
        batches.extend(b for b in (mfs_b, dd_b, ti_b) if b)
    return batches


def run_struct(space, meshes, batches, struct_dedup, cache_path):
    """Fresh engine per probe batch (as the corpus lifecycle sees it)
    sharing one scratch persistent cache — within-batch, cross-batch, and
    cross-engine structural dedup all count."""
    for suffix in ("", "-wal", "-shm"):
        try:
            os.remove(cache_path + suffix)
        except FileNotFoundError:
            pass
    cache = MeasureCache(cache_path)
    t0 = time.time()
    agg = {"n_compiles": 0, "n_failures": 0, "n_struct_hits": 0,
           "n_lowerings": 0, "compile_time": 0.0, "lower_time": 0.0,
           "n_attempts": 0}
    results = []
    for batch in batches:
        eng = Engine(space, meshes, n_workers=N_WORKERS,
                     persistent_cache=cache, struct_dedup=struct_dedup)
        results.append(eng.measure_batch(batch, prescreen=0))
        s = eng.stats()
        for k in agg:
            agg[k] += s[k]
        eng.close()
    agg["wall_s"] = time.time() - t0
    cache.close()
    return agg, results


def main():
    compile_cache.enable()
    space = SearchSpace(bench_archs(["qwen2-1.5b", "mixtral-8x7b"]),
                        BENCH_SHAPES,
                        restrict={"grad_compress": ("none",),
                                  "scan_layers": (True,)})
    meshes = bench_meshes()
    pool = sample_pool(space, POOL)
    phases = make_stream(pool)
    n_requests = sum(len(ph) for ph in phases)

    cache_path = os.path.join(RESULTS, "bench_throughput_cache.sqlite")
    for suffix in ("", "-wal", "-shm"):            # cold start
        try:
            os.remove(cache_path + suffix)
        except FileNotFoundError:
            pass

    serial_s, serial_compiles = run_serial(space, meshes, phases)
    opt_s, opt_compiles, hit_rate = run_optimized(space, meshes, phases,
                                                  cache_path)
    serial_pps = n_requests / serial_s
    opt_pps = n_requests / opt_s
    # ---- split-phase structural dedup on the campaign-probe stream
    probe_batches = campaign_probe_batches(space, N_WITNESSES)
    if SMOKE:                      # CI exercises the plumbing, not the
        capped = []                # acceptance number: cap compile count,
        left = 12                  # ddmin batches first (densest aliasing)
        for b in (probe_batches[1::3] + probe_batches[0::3]
                  + probe_batches[2::3]):
            capped.append(b[:left])
            left -= len(capped[-1])
            if left <= 0:
                break
        probe_batches = [b for b in capped if b]
    n_probe_pts = sum(len(b) for b in probe_batches)
    struct_cache = os.path.join(RESULTS, "bench_struct_cache.sqlite")
    off, res_off = run_struct(space, meshes, probe_batches,
                              struct_dedup=False, cache_path=struct_cache)
    on, res_on = run_struct(space, meshes, probe_batches,
                            struct_dedup=True, cache_path=struct_cache)
    assert res_on == res_off, "struct dedup changed counters"  # byte parity
    realized = on["n_compiles"] + on["n_failures"] + on["n_struct_hits"]
    struct = {
        "n_points": n_probe_pts,
        "n_witness_batches": len(probe_batches),
        "n_attempts": on["n_attempts"],
        "compiles_off": off["n_compiles"],
        "compiles_on": on["n_compiles"],
        "compiles_avoided": off["n_compiles"] - on["n_compiles"],
        "n_struct_hits": on["n_struct_hits"],
        "struct_hit_rate": on["n_struct_hits"] / max(realized, 1),
        "compile_time_off": off["compile_time"],
        "compile_time_on": on["compile_time"],
        "compile_time_saved": off["compile_time"] - on["compile_time"],
        "lower_time_on": on["lower_time"],
        "wall_off": off["wall_s"], "wall_on": on["wall_s"],
        "counters_identical": True,
    }

    out = {
        "n_requests": n_requests,
        "n_unique": len(pool),
        "n_phases": len(phases),
        "serial_s": serial_s, "serial_pps": serial_pps,
        "serial_compiles": serial_compiles,
        "optimized_s": opt_s, "optimized_pps": opt_pps,
        "optimized_compiles": opt_compiles,
        "speedup": opt_pps / serial_pps,
        "cache_hit_rate": hit_rate,
        "n_workers": N_WORKERS,
        "struct_dedup": struct,
    }
    # SMOKE runs (CI) must never clobber the committed full-scale artifact
    save_json(f"bench_engine_throughput{'_smoke' if SMOKE else ''}.json",
              out)
    print(f"bench_engine_throughput,serial={serial_pps:.2f}pps,"
          f"optimized={opt_pps:.2f}pps,speedup={out['speedup']:.1f}x,"
          f"hit_rate={hit_rate:.2f},"
          f"compiles={serial_compiles}->{opt_compiles}", flush=True)
    print(f"bench_engine_throughput,struct_dedup,"
          f"points={n_probe_pts},"
          f"compiles={struct['compiles_off']}->{struct['compiles_on']},"
          f"avoided={struct['compiles_avoided']},"
          f"hit_rate={struct['struct_hit_rate']:.2f},"
          f"compile_time_saved={struct['compile_time_saved']:.0f}s",
          flush=True)


if __name__ == "__main__":
    main()
