"""Concurrent engine invariants.

* determinism — same seed + same budget gives identical SearchResult
  events/anomalies for n_workers=1 vs n_workers=4 (all RNG stays in the
  driver thread; budget is charged at submission in list order);
* accounting — unique points charge budget once, failed compiles count as
  attempts, cache hits never recharge;
* dedup — duplicate points in a batch (or repeats across batches) compile
  once;
* persistence — a fresh engine warm-starts from the on-disk cache with zero
  recompiles, including remembered compile failures.

Engine-logic tests stub the compile layer (monkeypatched build_cell /
measure_cell) so they run in milliseconds; the determinism test compiles
real (smoke-scale) workloads end-to-end.
"""
import random

import pytest

import repro.core.engine as engine_mod
from repro.configs.all_archs import smoke_config
from repro.configs.base import ShapeSpec
from repro.core.engine import Engine
from repro.core.measure_cache import MeasureCache, space_fingerprint
from repro.core.sa import simulated_annealing
from repro.core.searchspace import SearchSpace


def small_space():
    archs = {n: smoke_config(n) for n in ["qwen2-1.5b"]}
    shapes = {"train_s": ShapeSpec("train_s", "train", 64, 8),
              "decode_s": ShapeSpec("decode_s", "decode", 256, 8)}
    return SearchSpace(archs, shapes, restrict={
        "optimizer": ("adamw",), "grad_compress": ("none",),
        "n_microbatch": (1, 2), "capacity_factor": (1.25,),
        "attn_impl": ("auto", "plain"), "remat": ("none", "dots")})


# --------------------------------------------------------- stubbed engines
class _StubMeasurement:
    perf = {"roofline_efficiency": 0.5}
    diag = {"collective_blowup": 1.0}


class _FakeLowered:
    """Stub LoweredCell: the fingerprint keys the realized cell, mirroring
    the real invariant (same cell -> same program -> same fingerprint)."""

    def __init__(self, cell):
        self.cell = cell
        self.fingerprint = "fp:" + repr(cell)


def _stub_compiles(monkeypatch, fail_on=()):
    """Replace the split-phase compile layer with instant deterministic
    stubs (lower_cell -> fingerprint, compile_lowered -> Measurement)."""
    calls = []

    def fake_build_cell(cfg, shape, policy, mesh, opt):
        return (cfg.name, shape.name, str(policy))

    def fake_lower_cell(cell, chip=None):
        return _FakeLowered(cell)

    def fake_compile_lowered(lc, chip=None):
        calls.append(lc.cell)
        if lc.cell[1] in fail_on:
            raise RuntimeError("planted compile failure")
        return _StubMeasurement()

    def fake_lowered_counters(lc, chip=None):
        return {"perf.roofline_efficiency": 0.5,
                "perf.useful_flops_ratio": 0.4,
                "diag.transpose_bytes": 1e6}

    monkeypatch.setattr(engine_mod, "build_cell", fake_build_cell)
    monkeypatch.setattr(engine_mod.counters_mod, "lower_cell",
                        fake_lower_cell)
    monkeypatch.setattr(engine_mod.counters_mod, "compile_lowered",
                        fake_compile_lowered)
    monkeypatch.setattr(engine_mod.counters_mod, "lowered_counters",
                        fake_lowered_counters)
    return calls


def test_unique_point_charges_once(monkeypatch):
    calls = _stub_compiles(monkeypatch)
    space = small_space()
    eng = Engine(space, {"single": object()}, persistent_cache=False)
    p = space.random_point(random.Random(0))
    p = {**p, "mesh": "single"}
    m1 = eng.measure(p)
    m2 = eng.measure(p)
    assert m1 is m2
    assert eng.n_attempts == 1
    assert eng.n_compiles == 1 and len(calls) == 1
    assert eng.n_cache_hits == 1


def test_failed_compile_counts_as_attempt(monkeypatch):
    _stub_compiles(monkeypatch, fail_on=("train_s", "decode_s"))
    space = small_space()
    eng = Engine(space, {"single": object()}, persistent_cache=False)
    p = {**space.random_point(random.Random(0)), "mesh": "single"}
    assert eng.measure(p) is None
    assert eng.measure(p) is None          # cached failure, no recharge
    assert eng.n_attempts == 1
    assert eng.n_failures == 1
    assert eng.failures == [
        "compile failed: RuntimeError: planted compile failure"]
    assert eng.n_compiles == 0
    s = eng.stats()
    assert s["n_attempts"] == 1 and s["n_failures"] == 1
    assert s["n_cache_hits"] == 1


def test_measure_batch_dedups_and_aligns(monkeypatch):
    calls = _stub_compiles(monkeypatch)
    space = small_space()
    eng = Engine(space, {"single": object()}, n_workers=4,
                 persistent_cache=False)
    rng = random.Random(1)
    pts = []
    while len(pts) < 3:
        p = {**space.random_point(rng), "mesh": "single"}
        if all(space.point_key(p) != space.point_key(q) for q in pts):
            pts.append(p)
    batch = [pts[0], pts[1], pts[0], pts[2], pts[1]]
    results = eng.measure_batch(batch)
    assert len(results) == 5
    assert results[0] is results[2] and results[1] is results[4]
    assert len(calls) == 3                 # unique points compile once
    assert eng.n_attempts == 3


def test_persistent_cache_warm_start(monkeypatch, tmp_path):
    calls = _stub_compiles(monkeypatch, fail_on=("decode_s",))
    space = small_space()
    cache_path = str(tmp_path / "cache.sqlite")
    rng = random.Random(2)
    pts = [{**space.random_point(rng), "mesh": "single"} for _ in range(6)]

    cold = Engine(space, {"single": object()}, persistent_cache=cache_path)
    cold_results = cold.measure_batch(pts)
    n_cold_compiled = len(calls)
    assert n_cold_compiled > 0

    warm = Engine(space, {"single": object()}, persistent_cache=cache_path)
    warm_results = warm.measure_batch(pts)
    assert len(calls) == n_cold_compiled   # zero recompiles, incl. failures
    assert warm.n_compiles == 0 and warm.n_failures == 0
    assert warm.n_disk_hits > 0
    for c, w in zip(cold_results, warm_results):
        if c is None:
            assert w is None
        else:
            flat = {k: v for k, v in c.items() if not k.startswith("_")}
            assert w == flat
    # warm run charges the same budget as cold: trajectories are identical
    assert warm.n_attempts == cold.n_attempts


def test_collie_cache_env_var(monkeypatch, tmp_path):
    _stub_compiles(monkeypatch)
    monkeypatch.setenv("COLLIE_CACHE", str(tmp_path / "envcache.sqlite"))
    space = small_space()
    eng = Engine(space, {"single": object()})
    assert eng.persistent is not None
    p = {**space.random_point(random.Random(3)), "mesh": "single"}
    eng.measure(p)
    assert eng.persistent.size(eng.space_fp) == 1


def test_space_fingerprint_sensitivity():
    space = small_space()
    fp1 = space_fingerprint(space)
    other = SearchSpace({n: smoke_config(n) for n in ["qwen2-1.5b"]},
                        {"train_s": ShapeSpec("train_s", "train", 128, 8)})
    assert fp1 != space_fingerprint(other)
    assert fp1 == space_fingerprint(small_space())


def test_measure_cache_roundtrip(tmp_path):
    mc = MeasureCache(str(tmp_path / "mc.sqlite"))
    key = (("arch", "a"), ("shape", "s"), ("flag", True), ("n", 4))
    assert mc.get("fp", key) == (False, None)
    mc.put("fp", key, {"perf.x": 1.5, "diag.n": 2, "_measurement": object()})
    found, val = mc.get("fp", key)
    assert found and val == {"perf.x": 1.5, "diag.n": 2}
    mc.put("fp", key, None)                # failures are remembered
    assert mc.get("fp", key) == (True, None)
    assert mc.size() == 1
    mc.clear()
    assert mc.size() == 0
    mc.close()


# ------------------------------------------------------ real-compile test
@pytest.mark.slow
def test_search_identical_across_n_workers():
    """Same seed + budget => identical anomalies/events for 1 vs 4 workers."""
    from repro.launch.mesh import make_host_mesh

    space = small_space()
    mesh = make_host_mesh()
    runs = {}
    for nw in (1, 4):
        eng = Engine(space, {"single": mesh}, n_workers=nw,
                     persistent_cache=False)
        runs[nw] = simulated_annealing(
            eng, space, "diag.collective_blowup", "max", seed=5,
            budget_compiles=14)
    a, b = runs[1], runs[4]
    assert len(a.events) == len(b.events)
    for ea, eb in zip(a.events, b.events):
        assert ea.point == eb.point
        assert ea.kinds == eb.kinds
        assert ea.counter_value == eb.counter_value
        assert ea.n_spent == eb.n_spent
        assert (ea.new_mfs is None) == (eb.new_mfs is None)
    assert len(a.anomalies) == len(b.anomalies)
    for ma, mb in zip(a.anomalies, b.anomalies):
        assert ma.kind == mb.kind
        assert ma.conditions == mb.conditions
        assert ma.witness == mb.witness
    assert a.n_attempts == b.n_attempts
