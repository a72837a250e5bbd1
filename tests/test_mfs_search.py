"""MFS + search-algorithm properties against a SYNTHETIC oracle (no compiles).

A FakeEngine plants hidden conjunctive trigger rules (like the paper's
hardware anomalies); hypothesis then checks the paper-critical invariants:

* soundness   — every point matching a constructed MFS reproduces the anomaly;
* necessity   — every factor in the MFS has a rejected alternative value;
* pruning     — with MFS-skip enabled, the search never re-measures a point
                inside a known anomaly region;
* discovery   — counter-guided SA finds a planted anomaly at least as fast as
                random search on average (the paper's Fig.4 claim, in small).
"""
import itertools
import random

import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import ShapeSpec
from repro.configs.all_archs import smoke_config
from repro.core import anomaly as anomaly_mod
from repro.core.mfs import MFS, construct_mfs, match_any
from repro.core.random_search import random_search
from repro.core.sa import simulated_annealing
from repro.core.searchspace import SearchSpace

ARCHS = {n: smoke_config(n) for n in ["qwen2-1.5b", "rwkv6-7b"]}
SHAPES = {"train_s": ShapeSpec("train_s", "train", 64, 8),
          "decode_s": ShapeSpec("decode_s", "decode", 256, 8)}


def make_space():
    return SearchSpace(ARCHS, SHAPES)


class FakeEngine:
    """Synthetic subsystem: hidden rule -> anomaly + correlated counter."""

    def __init__(self, space, rule: dict, kind="A2"):
        self.space = space
        self.rule = rule          # factor -> triggering value set
        self.kind = kind
        self.n_compiles = 0
        self.compile_time = 0.0
        self.measured = []

    def _match_frac(self, p):
        hits = sum(p.get(f) in vs for f, vs in self.rule.items())
        return hits / max(len(self.rule), 1)

    def measure(self, p):
        p = self.space.normalize(p)
        if not self.space.valid(p):
            return None
        self.n_compiles += 1
        self.measured.append(dict(p))
        frac = self._match_frac(p)
        trig = frac == 1.0
        out = {
            "perf.roofline_efficiency": 0.1 if trig else 0.6 - 0.2 * frac,
            "perf.useful_flops_ratio": 0.9,
            "diag.collective_blowup": 1.0 + 2.5 * frac,  # guides (below thr)
            "diag.hbm_oversubscribed": 0.5,
        }
        if trig and self.kind == "A2":
            out["diag.collective_blowup"] = 20.0
        if trig and self.kind == "A4":
            out["diag.hbm_oversubscribed"] = 2.0
        return out


@st.composite
def hidden_rules(draw):
    from repro.core.searchspace import UNCOUPLED
    space = make_space()
    n = draw(st.integers(1, 3))
    factors = draw(st.permutations(sorted(UNCOUPLED)))[:n]
    rule = {}
    for f in factors:
        dom = space.factors[f]
        k = draw(st.integers(1, max(1, len(dom) - 1)))
        rule[f] = frozenset(draw(st.permutations(dom))[:k])
    return rule


@given(hidden_rules(), st.integers(0, 100))
@settings(max_examples=25, deadline=None)
def test_mfs_soundness_and_necessity(rule, seed):
    space = make_space()
    eng = FakeEngine(space, rule)
    rng = random.Random(seed)
    # find a triggering witness
    witness = None
    for _ in range(4000):
        p = space.random_point(rng)
        m = eng.measure(p)
        if m and "A2" in anomaly_mod.kinds(m, p["remat"]):
            witness = p
            break
    if witness is None:
        return                      # rule unreachable under validity; fine
    mfs = construct_mfs(eng, space, witness, "A2", eng.measure(witness))
    # soundness: points matching the MFS reproduce the anomaly
    for _ in range(50):
        q = space.random_point(rng)
        for f, vals in mfs.conditions.items():
            q[f] = rng.choice(list(vals))
        q = space.normalize(q)
        if not mfs.matches(q) or not space.valid(q):
            continue                 # normalization/validity moved q outside
        m = eng.measure(q)
        assert m is not None and "A2" in anomaly_mod.kinds(m, q["remat"])
    # necessity: each MFS factor has an excluded alternative
    for f, vals in mfs.conditions.items():
        assert set(vals) != set(space.factors[f])


def test_sa_skip_flag_effect():
    """With mfs_skip, once an anomaly region is known the SA loop avoids it."""
    space = make_space()
    rule = {"preset": frozenset(["dp"])}
    eng = FakeEngine(space, rule)
    r = simulated_annealing(eng, space, "diag.collective_blowup", "max",
                            seed=0, budget_compiles=150, mfs_skip=True,
                            mfs_construct=True)
    assert r.anomalies, "planted anomaly not found"
    mfs = r.anomalies[0]
    assert "preset" in mfs.conditions
    assert set(mfs.conditions["preset"]) == {"dp"}
    # events after the MFS event must not match it (search loop skip)
    seen_mfs = False
    violations = 0
    for e in r.events:
        if e.new_mfs is not None:
            seen_mfs = True
            continue
        if seen_mfs and mfs.matches(e.point) and e.new_mfs is None:
            violations += 1
    assert violations == 0


def test_counter_guidance_beats_random():
    """Paper Fig.4 in miniature: on a *complicated* (6-condition) planted
    anomaly, counter-guided SA needs fewer measurements than random fuzzing
    (deterministic given the fixed seeds)."""
    rule = {"preset": frozenset(["tp"]), "scan_layers": frozenset([False]),
            "mesh": frozenset(["multi"]), "vocab_shard": frozenset([False]),
            "cache_shard": frozenset([False]), "seq_shard": frozenset([False])}

    def first_hit(search_fn, seed):
        eng = FakeEngine(make_space(), rule)
        r = search_fn(eng, seed)
        for e in r.events:
            if e.kinds:
                return e.n_spent
        return 1500

    sa_hits = [first_hit(lambda e, s: simulated_annealing(
        e, make_space(), "diag.collective_blowup", "max", seed=s,
        budget_compiles=1500, mfs_construct=False, t0=0.5), s)
        for s in range(10)]
    rnd_hits = [first_hit(lambda e, s: random_search(
        e, make_space(), seed=s, budget_compiles=1500, mfs_construct=False), s)
        for s in range(10)]
    assert sum(sa_hits) < sum(rnd_hits), (sa_hits, rnd_hits)


def test_match_any():
    mfs = MFS("A1", {"preset": ("dp",), "mesh": ("multi",)}, {})
    assert mfs.matches({"preset": "dp", "mesh": "multi", "x": 1})
    assert not mfs.matches({"preset": "tp", "mesh": "multi"})
    assert match_any([mfs], {"preset": "dp", "mesh": "multi"})


def test_matches_missing_factor_is_conservative():
    """A point that omits a conditioned factor can never match: the MFS
    claims nothing about partial points (skip logic must not skip them)."""
    mfs = MFS("A1", {"preset": ("dp",), "mesh": ("multi",)}, {})
    assert not mfs.matches({"preset": "dp"})          # mesh missing
    assert not mfs.matches({})
    assert not match_any([mfs], {"mesh": "multi"})
    # None is not a triggering value either
    assert not mfs.matches({"preset": None, "mesh": "multi"})


def test_matches_unnormalized_point_differs_from_normalized():
    """matches() is literal: conditions are built on *normalized* points, so
    callers must normalize first.  A decode-cell point with a scrambled
    train-only factor demonstrates the trap — and that normalize fixes it."""
    space = make_space()
    rng = random.Random(0)
    w = space.normalize({**space.random_point(rng), "shape": "decode_s"})
    assert w["remat"] == "none"                       # pinned by normalize
    mfs = MFS("A2", {"remat": ("none",), "shape": ("decode_s",)}, dict(w))
    raw = {**w, "remat": "full"}                      # un-normalized decode
    assert not mfs.matches(raw)                       # literal comparison
    assert mfs.matches(space.normalize(raw))          # same workload, matches


def test_construct_mfs_budget_exhaustion_still_well_formed():
    """max_probes=1: a budget-starved construction measures one probe yet
    returns a conservative, self-consistent MFS (paper: budget exhaustion
    must lose information, not invent it)."""
    space = make_space()
    rule = {"preset": frozenset(["dp"]), "seq_shard": frozenset([False])}
    eng = FakeEngine(space, rule)
    rng = random.Random(2)
    witness = None
    for _ in range(4000):
        p = space.random_point(rng)
        m = eng.measure(p)
        if m and "A2" in anomaly_mod.kinds(m, p["remat"]):
            witness = p
            break
    assert witness is not None
    n_before = eng.n_compiles
    mfs = construct_mfs(eng, space, witness, "A2", eng.measure(witness),
                        fidelity="prescreen", max_probes=1)
    assert mfs.n_tests == 1                           # exactly one probe
    assert eng.n_compiles - n_before <= 2             # probe + witness remeasure
    assert mfs.matches(witness)                       # witness always inside
    full = construct_mfs(eng, space, witness, "A2", eng.measure(witness))
    for f, vals in mfs.conditions.items():
        assert witness[f] in vals
        # conservative: triggering sets only shrink vs the full construction
        assert set(vals) <= set(full.conditions.get(f, space.factors[f]))
    # every factor the full construction conditioned on is still conditioned
    assert set(full.conditions) <= set(mfs.conditions)
