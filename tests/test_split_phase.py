"""Split-phase measurement + structural dedup + fidelity-1 tier (ISSUE 5).

* structural dedup — two points that lower to the same fingerprint compile
  once (within a batch, across batches, across engines via the persistent
  ``structs`` table) yet charge budget independently and return identical
  flat dicts;
* measure_full — the disk-hit path rebuilds the Measurement with exactly
  one recompile and correct ``n_compiles`` accounting;
* counter_names — counter discovery is uncharged;
* MeasureCache — ``get_many`` batched reads, structs/point_fps roundtrip,
  ``clear`` covers all three tables;
* fidelity-1 "lowered" tier — ``measure_lowered`` serves structural
  estimates uncharged; ``construct_mfs``/``minimize_witness``/
  ``tighten_conditions`` short-circuit fingerprint-identical probes;
* calibration persistence — both calibrator channels survive a save/load
  roundtrip, and old single-channel files still load.

Engine-logic tests stub the compile layer (see test_engine_concurrency);
`slow`-marked tests verify the fingerprint semantics on real compiles.
"""
import json
import random

import pytest

import repro.core.engine as engine_mod
from repro import hw
from repro.configs.all_archs import smoke_config
from repro.configs.base import ShapeSpec
from repro.core.engine import Engine
from repro.core.measure_cache import MeasureCache, point_key_str
from repro.core.mfs import construct_mfs
from repro.core.minimize import minimize_witness
from repro.core.searchspace import SearchSpace
from repro.core.surrogate import Surrogate


def small_space():
    archs = {n: smoke_config(n) for n in ["qwen2-1.5b"]}
    shapes = {"train_s": ShapeSpec("train_s", "train", 64, 8),
              "decode_s": ShapeSpec("decode_s", "decode", 256, 8)}
    return SearchSpace(archs, shapes, restrict={
        "optimizer": ("adamw",), "grad_compress": ("none",),
        "n_microbatch": (1, 2), "capacity_factor": (1.25,),
        "attn_impl": ("auto", "plain"), "remat": ("none", "dots")})


class _StubMeasurement:
    def __init__(self, h):
        self.perf = {"roofline_efficiency": 0.2 + (h % 7) * 0.1,
                     "useful_flops_ratio": 0.3 + (h % 5) * 0.1}
        self.diag = {"collective_blowup": 9.0,        # every point anomalous
                     "memory_overshoot": 1.0 + (h % 3),
                     "hbm_oversubscribed": 0.4}


class _FakeLowered:
    def __init__(self, cell, fp):
        self.cell = cell
        self.fingerprint = fp


def _stub_compiles(monkeypatch, fp_of=None, fail_on=()):
    """Split-phase stub; ``fp_of(cell) -> fingerprint`` controls aliasing
    (default: the cell itself, i.e. fp-equal ⟺ to_run-equal)."""
    calls = []

    def fake_build_cell(cfg, shape, policy, mesh, opt):
        return (cfg.name, shape.name, str(policy))

    def fake_lower_cell(cell, chip=None):
        fp = "fp:" + (repr(cell) if fp_of is None else fp_of(cell))
        return _FakeLowered(cell, fp)

    def fake_compile_lowered(lc, chip=None):
        calls.append(lc.cell)
        if lc.cell[1] in fail_on:
            raise RuntimeError("planted compile failure")
        return _StubMeasurement(sum(map(ord, "".join(map(str, lc.cell)))))

    def fake_lowered_counters(lc, chip=None):
        h = sum(map(ord, "".join(map(str, lc.cell))))
        return {"perf.roofline_efficiency": 0.1 + (h % 11) * 0.05,
                "perf.useful_flops_ratio": 0.2 + (h % 7) * 0.05,
                "diag.transpose_bytes": float(h % 13) * 1e5}

    monkeypatch.setattr(engine_mod, "build_cell", fake_build_cell)
    monkeypatch.setattr(engine_mod.counters_mod, "lower_cell",
                        fake_lower_cell)
    monkeypatch.setattr(engine_mod.counters_mod, "compile_lowered",
                        fake_compile_lowered)
    monkeypatch.setattr(engine_mod.counters_mod, "lowered_counters",
                        fake_lowered_counters)
    return calls


def _aliasing_pair(space):
    """Two points with distinct keys whose stub cells are identical: the
    stub cell ignores the mesh kind, so a mesh flip aliases structurally."""
    p = {**space.random_point(random.Random(0)), "mesh": "single"}
    q = {**p, "mesh": "multi"}
    p, q = space.normalize(p), space.normalize(q)
    assert space.point_key(p) != space.point_key(q)
    return p, q


def _meshes():
    return {"single": object(), "multi": object()}


# ------------------------------------------------------- structural dedup
def test_struct_dedup_one_compile_identical_dicts_independent_charge(
        monkeypatch):
    calls = _stub_compiles(monkeypatch)
    space = small_space()
    eng = Engine(space, _meshes(), persistent_cache=False)
    p, q = _aliasing_pair(space)
    rp, rq = eng.measure_batch([p, q])
    assert rp is not None and rp == rq        # identical flat dicts
    assert len(calls) == 1                    # ... from ONE compile
    assert eng.n_compiles == 1
    assert eng.n_struct_hits == 1
    assert eng.n_lowerings == 2               # both points were lowered
    assert eng.n_attempts == 2                # budget charged per point
    s = eng.stats()
    assert s["n_struct_hits"] == 1 and s["n_lowerings"] == 2
    eng.close()


def test_struct_dedup_across_engines_via_persistent_cache(monkeypatch,
                                                          tmp_path):
    calls = _stub_compiles(monkeypatch)
    space = small_space()
    path = str(tmp_path / "c.sqlite")
    p, q = _aliasing_pair(space)
    e1 = Engine(space, _meshes(), persistent_cache=path)
    assert e1.measure(p) is not None
    assert e1.persistent.struct_size(e1.space_fp) == 1
    assert e1.persistent.get_fp(e1.space_fp, space.point_key(p)) is not None
    e1.close()
    # a NEW point (never measured) that lowers to a known fingerprint is
    # served from the structs table without compiling
    e2 = Engine(space, _meshes(), persistent_cache=path)
    r = e2.measure(q)
    assert r is not None and len(calls) == 1
    assert e2.n_compiles == 0 and e2.n_struct_hits == 1
    assert e2.n_disk_hits == 0                # not a point hit: a struct hit
    e2.close()


def test_struct_dedup_disabled_compiles_both(monkeypatch):
    calls = _stub_compiles(monkeypatch)
    space = small_space()
    eng = Engine(space, _meshes(), persistent_cache=False,
                 struct_dedup=False)
    p, q = _aliasing_pair(space)
    rp, rq = eng.measure_batch([p, q])
    assert rp == rq and len(calls) == 2 and eng.n_struct_hits == 0
    eng.close()


def test_collie_struct_env_default(monkeypatch):
    _stub_compiles(monkeypatch)
    space = small_space()
    assert Engine(space, _meshes(), persistent_cache=False).struct_dedup
    monkeypatch.setenv("COLLIE_STRUCT", "0")
    assert not Engine(space, _meshes(),
                      persistent_cache=False).struct_dedup


def test_struct_dedup_shares_planted_failures(monkeypatch):
    calls = _stub_compiles(monkeypatch, fail_on=("train_s", "decode_s"))
    space = small_space()
    eng = Engine(space, _meshes(), persistent_cache=False)
    p, q = _aliasing_pair(space)
    assert eng.measure(p) is None
    assert eng.measure(q) is None             # shared failure, no recompile
    assert len(calls) == 1 and eng.n_failures == 1
    assert eng.n_struct_hits == 1 and eng.n_attempts == 2
    eng.close()


# ----------------------------------------------------------- measure_full
def test_measure_full_rebuilds_from_disk_hit(monkeypatch, tmp_path):
    calls = _stub_compiles(monkeypatch)
    space = small_space()
    path = str(tmp_path / "c.sqlite")
    p = {**space.random_point(random.Random(1)), "mesh": "single"}
    cold = Engine(space, _meshes(), persistent_cache=path)
    flat = cold.measure(p)
    cold.close()
    warm = Engine(space, _meshes(), persistent_cache=path)
    assert warm.measure(p) == flat            # disk hit: counters only
    assert warm.n_disk_hits == 1 and warm.n_compiles == 0
    m = warm.measure_full(p)                  # rebuild = exactly 1 recompile
    assert isinstance(m, _StubMeasurement)
    assert warm.n_compiles == 1 and len(calls) == 2
    assert warm.measure_full(p) is m          # served from the meas store
    assert warm.n_compiles == 1
    assert warm.n_attempts == 1               # budget charged once, on measure
    warm.close()


def test_measure_full_bypasses_struct_dedup(monkeypatch):
    calls = _stub_compiles(monkeypatch)
    space = small_space()
    eng = Engine(space, _meshes(), persistent_cache=False)
    p, q = _aliasing_pair(space)
    eng.measure(p)
    assert eng.measure(q) is not None and len(calls) == 1  # struct hit
    m = eng.measure_full(q)                   # needs the real artifact
    assert isinstance(m, _StubMeasurement) and len(calls) == 2
    eng.close()


# ---------------------------------------------------------- counter_names
def test_counter_names_uncharged(monkeypatch):
    _stub_compiles(monkeypatch)
    space = small_space()
    eng = Engine(space, _meshes(), persistent_cache=False)
    p = {**space.random_point(random.Random(2)), "mesh": "single"}
    names = eng.counter_names(p)
    assert "perf.roofline_efficiency" in names["perf"]
    assert eng.n_attempts == 0                # discovery consumed no budget
    assert eng.n_compiles == 1                # ... but did measure once
    assert eng.measure(p) is not None         # a later real measure ...
    assert eng.n_attempts == 1                # ... charges normally
    assert eng.n_compiles == 1                # cache hit, no recompile
    eng.close()


# ------------------------------------------------------------ MeasureCache
def test_get_many_batched_reads(tmp_path):
    mc = MeasureCache(str(tmp_path / "mc.sqlite"))
    keys = [(("arch", "a"), ("n", i)) for i in range(950)]
    mc.put_many("fp", [(k, {"perf.x": float(i)} if i % 5 else None)
                       for i, k in enumerate(keys)])
    got = mc.get_many("fp", keys + [(("arch", "a"), ("n", -1))])
    assert len(got) == 950                    # absent key is absent, not None
    for i, k in enumerate(keys):
        assert got[point_key_str(k)] == ({"perf.x": float(i)} if i % 5
                                         else None)
    assert mc.get_many("fp", []) == {}
    mc.close()


def test_struct_tables_roundtrip_and_clear(tmp_path):
    mc = MeasureCache(str(tmp_path / "mc.sqlite"))
    mc.put_structs("fp", [("aaa", {"perf.x": 1.0}), ("bbb", None)])
    mc.put_fps("fp", [((("arch", "a"),), "aaa")])
    assert mc.get_struct("fp", "aaa") == (True, {"perf.x": 1.0})
    assert mc.get_struct("fp", "bbb") == (True, None)   # remembered failure
    assert mc.get_struct("fp", "ccc") == (False, None)
    assert mc.get_fp("fp", (("arch", "a"),)) == "aaa"
    assert mc.get_fp("fp", (("arch", "z"),)) is None
    assert mc.struct_size("fp") == 2 and mc.struct_size() == 2
    mc.clear("other")
    assert mc.struct_size("fp") == 2
    mc.clear()
    assert mc.struct_size() == 0
    assert mc.get_fp("fp", (("arch", "a"),)) is None
    mc.close()


def test_engine_batches_struct_writes(monkeypatch, tmp_path):
    """A measure_batch flushes struct + fp rows in one txn each."""
    _stub_compiles(monkeypatch)
    space = small_space()
    eng = Engine(space, _meshes(), n_workers=4,
                 persistent_cache=str(tmp_path / "c.sqlite"))
    n_calls = {"structs": 0, "fps": 0}
    orig_s, orig_f = eng.persistent.put_structs, eng.persistent.put_fps

    def spy_s(fp, items):
        n_calls["structs"] += 1
        return orig_s(fp, items)

    def spy_f(fp, items):
        n_calls["fps"] += 1
        return orig_f(fp, items)

    monkeypatch.setattr(eng.persistent, "put_structs", spy_s)
    monkeypatch.setattr(eng.persistent, "put_fps", spy_f)
    rng = random.Random(3)
    eng.measure_batch([{**space.random_point(rng), "mesh": "single"}
                       for _ in range(6)])
    assert n_calls["structs"] == 1 and n_calls["fps"] == 1
    assert eng.persistent.struct_size(eng.space_fp) > 0
    eng.close()


# ------------------------------------------------------------- fidelity 1
def test_measure_lowered_uncharged_and_cached(monkeypatch):
    calls = _stub_compiles(monkeypatch)
    space = small_space()
    eng = Engine(space, _meshes(), persistent_cache=False)
    p = {**space.random_point(random.Random(4)), "mesh": "single"}
    lo = eng.measure_lowered(p)
    assert lo is not None and "perf.useful_flops_ratio" in lo
    assert "diag.collective_blowup" in lo     # surrogate overlay present
    assert eng.n_attempts == 0 and eng.n_compiles == 0 and not calls
    assert eng.n_lowerings == 1
    eng.measure_lowered(p)                    # cached: no second lowering
    assert eng.n_lowerings == 1
    assert eng.stats()["n_lowered_served"] == 2
    bad = {**p, "mesh": "missing"}
    assert eng.measure_lowered(bad) is None
    # batch helper aligns and dedups
    outs = eng.measure_lowered_batch([p, bad, p])
    assert outs[0] == outs[2] is not None and outs[1] is None
    eng.close()


def test_lowered_key_persisted_across_engines(monkeypatch, tmp_path):
    _stub_compiles(monkeypatch)
    space = small_space()
    path = str(tmp_path / "c.sqlite")
    eng = Engine(space, _meshes(), persistent_cache=path)
    p, q = _aliasing_pair(space)
    assert eng.lowered_key(p) == eng.lowered_key(q)     # aliasing pair
    assert eng.n_lowerings == 2
    fp = eng.lowered_key(p)
    eng.measure(p)                            # persists the key -> fp row
    eng.close()
    eng2 = Engine(space, _meshes(), persistent_cache=path)
    assert eng2.lowered_key(p) == fp          # served from point_fps ...
    assert eng2.n_lowerings == 0              # ... without lowering
    eng2.close()


def test_lowered_feeds_second_calibrator_channel(monkeypatch):
    _stub_compiles(monkeypatch)
    space = small_space()
    eng = Engine(space, _meshes(), persistent_cache=False)
    p = {**space.random_point(random.Random(5)), "mesh": "single"}
    eng.measure_lowered(p)
    assert eng.surrogate.lowered_calibrator.n_observed == 0
    eng.measure(p)                            # real measurement observed
    assert eng.surrogate.lowered_calibrator.n_observed == 1
    eng.close()


def test_construct_mfs_lowered_fp_short_circuit(monkeypatch):
    """Probes that lower to the witness's fingerprint join the triggering
    set without a measurement; a fidelity="full" construction on the same
    witness measures strictly more probes."""
    # fingerprints ignore scan_layers: flipping it aliases structurally
    def fp_of(cell):
        return repr(cell).replace("scan_layers=False", "scan_layers=True")

    space = small_space()
    rng = random.Random(6)
    p = space.normalize({**space.random_point(rng), "mesh": "single"})

    _stub_compiles(monkeypatch, fp_of=fp_of)
    e_full = Engine(space, _meshes(), persistent_cache=False)
    full = construct_mfs(e_full, space, p, "A2", fidelity="full")
    e_low = Engine(space, _meshes(), persistent_cache=False)
    low = construct_mfs(e_low, space, p, "A2", fidelity="lowered")
    assert low.n_tests < full.n_tests         # the flip was not measured
    assert e_low.n_attempts < e_full.n_attempts
    # every kind-A2 stub counter is identical across cells, so conditions
    # must agree: the shortcut is a proof, not a heuristic
    assert low.conditions == full.conditions
    e_full.close()
    e_low.close()


def test_minimize_lowered_fp_short_circuit(monkeypatch):
    def fp_of(cell):
        return repr(cell).replace("scan_layers=False", "scan_layers=True")

    space = small_space()
    base = space.normalize({
        "mesh": "single", "remat": "none", "n_microbatch": 1,
        "params_f32": True, "zero1": True, "optimizer": "adamw",
        "grad_compress": "none", "preset": "fsdp", "seq_shard": True,
        "cache_shard": True, "vocab_shard": True, "scan_layers": False,
        "attn_impl": "auto", "capacity_factor": 1.25,
        "arch": "qwen2-1.5b", "shape": "train_s"})

    _stub_compiles(monkeypatch, fp_of=fp_of)
    e_full = Engine(space, _meshes(), persistent_cache=False)
    r_full = minimize_witness(e_full, space, base, "A2", fidelity="full")
    e_low = Engine(space, _meshes(), persistent_cache=False)
    r_low = minimize_witness(e_low, space, base, "A2", fidelity="lowered")
    assert r_low.triggered and r_full.triggered
    assert r_low.point == r_full.point        # same minimized witness
    assert r_low.n_probes <= r_full.n_probes  # scan_layers probe was free
    assert e_low.n_attempts < e_full.n_attempts
    e_full.close()
    e_low.close()


# ------------------------------------------------- calibration persistence
def test_two_channel_calibration_roundtrip(monkeypatch, tmp_path):
    _stub_compiles(monkeypatch)
    space = small_space()
    path = str(tmp_path / "calib.json")
    eng = Engine(space, _meshes(), persistent_cache=False,
                 calibrator_path=path)
    pts = [{**space.random_point(random.Random(7)), "mesh": "single"}
           for _ in range(10)]
    for p in pts:
        eng.measure_lowered(p)
    eng.measure_batch(pts)
    n0 = eng.surrogate.calibrator.n_observed
    n1 = eng.surrogate.lowered_calibrator.n_observed
    assert n0 > 0 and n1 > 0
    eng.close()                               # saves both channels
    eng2 = Engine(space, _meshes(), persistent_cache=False,
                  calibrator_path=path)
    assert eng2.surrogate.calibrator.n_observed == n0
    assert eng2.surrogate.lowered_calibrator.n_observed == n1
    eng2.close()
    # old single-channel files (plain Calibrator.state()) still load
    legacy = str(tmp_path / "legacy.json")
    with open(path) as f:
        doc = json.load(f)
    doc.pop("lowered")
    with open(legacy, "w") as f:
        json.dump(doc, f)
    sur = Surrogate(space, {"single": {}}, hw.V5E)
    assert sur.load_calibration(legacy)
    assert sur.calibrator.n_observed == n0
    assert sur.lowered_calibrator.n_observed == 0


# ------------------------------------------------------ real-compile tests
@pytest.mark.slow
def test_struct_dedup_real_compile_aliasing():
    """A rule override that doesn't change the chosen specs (cache_shard on
    a train cell) lowers to a byte-identical program: one compile serves
    both points with identical counters, cross-engine via the cache."""
    from repro.launch.mesh import make_host_mesh

    space = small_space()
    mesh = make_host_mesh()
    base = space.normalize({
        "mesh": "single", "remat": "none", "n_microbatch": 1,
        "params_f32": True, "zero1": True, "optimizer": "adamw",
        "grad_compress": "none", "preset": "fsdp", "seq_shard": True,
        "cache_shard": True, "vocab_shard": True, "scan_layers": True,
        "attn_impl": "auto", "capacity_factor": 1.25,
        "arch": "qwen2-1.5b", "shape": "train_s"})
    alias = space.normalize({**base, "cache_shard": False})
    assert space.point_key(alias) != space.point_key(base)
    eng = Engine(space, {"single": mesh}, n_workers=2,
                 persistent_cache=False)
    r = eng.measure_batch([base, alias])
    assert r[0] == r[1] is not None
    assert eng.n_compiles == 1 and eng.n_struct_hits == 1
    assert eng.n_attempts == 2
    # dedup off: both compile, counters still identical (the construction
    # claim the fingerprint relies on)
    eng_off = Engine(space, {"single": mesh}, persistent_cache=False,
                     struct_dedup=False)
    r_off = eng_off.measure_batch([base, alias])
    assert r_off[0] == r[0] and r_off[1] == r[1]
    assert eng_off.n_compiles == 2
    eng.close()
    eng_off.close()


@pytest.mark.slow
def test_measure_lowered_real():
    from repro.launch.mesh import make_host_mesh

    space = small_space()
    mesh = make_host_mesh()
    p = space.normalize({**space.random_point(random.Random(8)),
                         "mesh": "single"})
    eng = Engine(space, {"single": mesh}, persistent_cache=False)
    lo = eng.measure_lowered(p)
    assert lo is not None
    assert eng.n_compiles == 0 and eng.n_attempts == 0
    for k in ("perf.roofline_efficiency", "perf.useful_flops_ratio",
              "diag.transpose_bytes", "diag.collective_blowup"):
        assert k in lo and float(lo[k]) >= 0.0
    eng.close()
