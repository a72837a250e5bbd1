"""Surrogate (fidelity-0) quality + calibrator invariants (ISSUE 2).

The quality bar runs against the committed bench-scale measurement fixture
``benchmarks/results/bench_fidelity_pairs.json`` (every point the
ground-truth campaign of bench_fidelity.py measured, regenerated at bench
scale): Spearman rank correlation >= 0.6 between compile-free predictions
and measured values for each screened counter, and the online residual
calibrator must strictly improve mean absolute error after 32 observations.
Predictions need no devices — mesh information is static axis shapes — so
this runs in the tier-1 suite without a single compile.
"""
import json
import math
import os

import pytest

from repro.core.benchscale import BENCH_SHAPES, bench_archs
from repro import hw
from repro.core.searchspace import SearchSpace
from repro.core.surrogate import (Calibrator, KIND_COUNTER, SCREENED,
                                  Surrogate)

FIXTURE = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                       "results", "bench_fidelity_pairs.json")

# counters the quality bar is asserted on (the ISSUE 2 screened set); the
# remaining SCREENED entries are ride-along estimates with no gate
GATED = (
    "perf.roofline_efficiency",
    "perf.useful_flops_ratio",
    "diag.collective_blowup",
    "diag.memory_overshoot",
    "diag.hbm_oversubscribed",
    "diag.n_allgather",
    "diag.n_allreduce",
    "diag.n_alltoall",
    "diag.n_permute",
)


def spearman(xs, ys):
    def rank(v):
        order = sorted(range(len(v)), key=lambda i: v[i])
        r = [0.0] * len(v)
        i = 0
        while i < len(order):
            j = i
            while j + 1 < len(order) and v[order[j + 1]] == v[order[i]]:
                j += 1
            for k in range(i, j + 1):
                r[order[k]] = (i + j) / 2
            i = j + 1
        return r
    rx, ry = rank(xs), rank(ys)
    n = len(xs)
    mx, my = sum(rx) / n, sum(ry) / n
    num = sum((a - mx) * (b - my) for a, b in zip(rx, ry))
    dx = sum((a - mx) ** 2 for a in rx) ** 0.5
    dy = sum((b - my) ** 2 for b in ry) ** 0.5
    return num / (dx * dy) if dx * dy else 0.0


def load_fixture():
    if not os.path.exists(FIXTURE):
        pytest.skip("bench_fidelity_pairs.json not generated yet "
                    "(run benchmarks/bench_fidelity.py)")
    with open(FIXTURE) as f:
        data = json.load(f)
    space = SearchSpace(
        bench_archs(data["archs"]), BENCH_SHAPES,
        restrict={k: tuple(v) for k, v in data["restrict"].items()})
    sur = Surrogate(space, data["mesh_shapes"], hw.V5E)
    pairs = [(p, m) for p, m in data["pairs"] if m]
    if len(pairs) < 30:
        pytest.skip(f"fixture too small ({len(pairs)} pairs)")
    return space, sur, pairs


def test_fixture_counters_rank_correlate():
    """Fidelity-0 predictions rank-correlate (rho >= 0.6) with measured
    values for every screened counter on the committed GT measurements."""
    _, sur, pairs = load_fixture()
    rhos = {}
    for c in GATED:
        xs, ys = [], []
        for p, m in pairs:
            pred = sur.predict(p, calibrated=False)
            if pred is not None and c in pred and m.get(c) is not None:
                xs.append(float(pred[c]))
                ys.append(float(m[c]))
        assert len(xs) >= 20, f"{c}: only {len(xs)} prediction pairs"
        if len(set(ys)) < 5:
            continue                   # degenerate at this bench subset
        rhos[c] = spearman(xs, ys)
    assert rhos, "no non-degenerate screened counters in fixture"
    bad = {c: r for c, r in rhos.items() if r < 0.6}
    assert not bad, f"Spearman below 0.6: {bad} (all: {rhos})"


def test_calibration_strictly_improves_mae():
    """After 32 observations the residual calibrator's corrected predictions
    have strictly lower mean absolute error than the raw ones."""
    _, sur, pairs = load_fixture()
    obs = pairs * max(1, math.ceil(32 / len(pairs)))
    assert len(obs) >= 32
    for p, m in obs:
        sur.observe(p, m)
    assert sur.calibrator.n_observed >= 32
    raw_err, cal_err, n = {}, {}, {}
    for p, m in pairs:
        raw = sur.predict(p, calibrated=False)
        cal = sur.predict(p, calibrated=True)
        if raw is None:
            continue
        for c in GATED:
            if c in raw and m.get(c) is not None:
                raw_err[c] = raw_err.get(c, 0.0) + abs(raw[c] - m[c])
                cal_err[c] = cal_err.get(c, 0.0) + abs(cal[c] - m[c])
                n[c] = n.get(c, 0) + 1
    # aggregate: normalized (per-counter scale-free) MAE must strictly drop
    raw_tot = sum(raw_err[c] / max(raw_err[c], cal_err[c], 1e-12)
                  for c in raw_err)
    cal_tot = sum(cal_err[c] / max(raw_err[c], cal_err[c], 1e-12)
                  for c in cal_err)
    assert cal_tot < raw_tot, (
        f"calibration did not improve MAE: raw={raw_tot} cal={cal_tot}")
    # and the majority of screened counters improve individually
    improved = sum(1 for c in raw_err if cal_err[c] < raw_err[c])
    assert improved >= len(raw_err) * 0.6, (
        f"only {improved}/{len(raw_err)} counters improved: "
        f"{ {c: (raw_err[c], cal_err[c]) for c in raw_err} }")


def test_predict_matches_engine_feasibility():
    """The surrogate returns None exactly where the engine would reject."""
    space, sur, pairs = load_fixture()
    import random
    rng = random.Random(0)
    for _ in range(50):
        p = space.random_point(rng)
        assert sur.predict(p) is not None      # valid points get estimates
    p = dict(pairs[0][0])
    p["mesh"] = "nonexistent"
    assert sur.predict(p) is None              # unknown mesh -> reject


def test_predictions_deterministic_and_complete():
    _, sur, pairs = load_fixture()
    p = pairs[0][0]
    a = sur.predict(p, calibrated=False)
    b = sur.predict(p, calibrated=False)
    assert a == b
    for c in SCREENED:
        assert c in a and math.isfinite(float(a[c])), c


def test_predict_batch_bit_identical_to_scalar():
    """ISSUE 5 satellite: the numpy-vectorized batch estimate is pinned
    BIT-identical (==, not allclose) to the scalar path on the committed
    fixture, including infeasible points and duplicate keys."""
    space, _, pairs = load_fixture()
    import random
    rng = random.Random(0)
    mesh_shapes = {"single": {"data": 4, "model": 4},
                   "multi": {"pod": 2, "data": 4, "model": 4}}
    pts = [p for p, _ in pairs]
    pts += [space.random_point(rng) for _ in range(100)]
    pts.append(dict(pts[0]))                       # duplicate key
    bad = dict(pts[1])
    bad["mesh"] = "nonexistent"
    pts.insert(5, bad)                             # infeasible row
    scalar = Surrogate(space, mesh_shapes, hw.V5E)
    vector = Surrogate(space, mesh_shapes, hw.V5E)
    want = [scalar.predict(p, calibrated=False) for p in pts]
    got = vector.predict_batch(pts, calibrated=False)
    assert want == got
    # calibrated outputs route through the same calibrator.apply
    for p, m in pairs[:40]:
        scalar.observe(p, m)
        vector.observe(p, m)
    assert [scalar.predict(p) for p in pts[:50]] \
        == vector.predict_batch(pts[:50])
    # the batch path populates the same raw cache the scalar path reads
    assert vector.predict(pts[0], calibrated=False) == want[0]


def test_kind_counter_map_covers_anomaly_kinds():
    from repro.core import anomaly
    assert set(KIND_COUNTER) == {"A1", "A2", "A3", "A4"}
    for c, mode in KIND_COUNTER.values():
        assert c in SCREENED
        assert mode in ("min", "max")
    assert anomaly.A1_EFFICIENCY_MIN > 0      # thresholds the score uses


def test_calibrator_roundtrip_and_degenerate_guard(tmp_path):
    cal = Calibrator(min_obs=4)
    # constant predictions (zero variance) -> offset-only correction
    for _ in range(6):
        cal.observe({"perf.roofline_efficiency": 0.5},
                    {"perf.roofline_efficiency": 0.7})
    a, b = cal.coeffs("perf.roofline_efficiency")
    assert a == 1.0 and b > 0              # log-space offset
    out = cal.apply({"perf.roofline_efficiency": 0.5})
    assert abs(out["perf.roofline_efficiency"] - 0.7) < 1e-9
    # persistence roundtrip
    path = str(tmp_path / "calib.json")
    cal.save(path)
    cal2 = Calibrator()
    assert cal2.load(path)
    assert cal2.coeffs("perf.roofline_efficiency") == (a, b)
    assert not Calibrator().load(str(tmp_path / "missing.json"))


def test_calibrator_fit_recovers_scale_offset():
    """The log-space fit recovers an exact power-law+scale relation."""
    cal = Calibrator(min_obs=8)
    for i in range(16):
        x = float(i)
        y = math.expm1(2.0 * math.log1p(x) + 0.5)
        cal.observe({"diag.collective_blowup": x},
                    {"diag.collective_blowup": y})
    a, b = cal.coeffs("diag.collective_blowup")
    assert abs(a - 2.0) < 1e-9 and abs(b - 0.5) < 1e-9
    out = cal.apply({"diag.collective_blowup": 3.0})
    assert abs(out["diag.collective_blowup"]
               - math.expm1(2.0 * math.log1p(3.0) + 0.5)) < 1e-9
