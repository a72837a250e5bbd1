import random

import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import ShapeSpec
from repro.configs.all_archs import smoke_config
from repro.core.searchspace import SearchSpace

ARCHS = {n: smoke_config(n) for n in
         ["qwen2-1.5b", "mixtral-8x7b", "rwkv6-7b"]}
SHAPES = {"train_s": ShapeSpec("train_s", "train", 64, 8),
          "long_s": ShapeSpec("long_s", "decode", 512, 1)}


@pytest.fixture
def space():
    return SearchSpace(ARCHS, SHAPES)


def test_size_is_large(space):
    assert space.size() > 1e5


def test_long_context_invalid_for_full_attention(space):
    p = space.random_point(random.Random(0))
    p["arch"] = "qwen2-1.5b"
    p["shape"] = "long_s"
    assert not space.valid(p)
    p["arch"] = "rwkv6-7b"
    assert space.valid(p)


def test_microbatch_divisibility(space):
    p = space.random_point(random.Random(0))
    p.update(shape="train_s", arch="qwen2-1.5b", grad_compress="none",
             mesh="single")
    p["n_microbatch"] = 4                  # divides global_batch 8
    assert space.valid(p)
    p["n_microbatch"] = 32                 # does not divide 8
    assert not space.valid(p)


def test_normalize_pins_inert_factors(space):
    rng = random.Random(1)
    p = space.random_point(rng)
    p["shape"] = "long_s"
    p["remat"] = "full"
    p["n_microbatch"] = 16
    q = space.normalize(p)
    assert q["remat"] == "none" and q["n_microbatch"] == 1


@given(st.integers(0, 1000))
@settings(max_examples=50, deadline=None)
def test_random_points_valid(seed):
    space = SearchSpace(ARCHS, SHAPES)
    p = space.random_point(random.Random(seed))
    assert space.valid(p)
    assert p == space.normalize(p)


@given(st.integers(0, 1000))
@settings(max_examples=50, deadline=None)
def test_mutation_valid_and_local(seed):
    space = SearchSpace(ARCHS, SHAPES)
    rng = random.Random(seed)
    p = space.random_point(rng)
    q = space.mutate(p, rng)
    assert space.valid(q)
    assert q == space.normalize(q)
    # locality: at most 1 non-pinned factor differs (normalization may pin
    # additional factors when arch/shape changed)
    diffs = [k for k in p if p[k] != q[k]]
    explicit = [k for k in diffs
                if k in ("arch", "shape", "mesh", "preset", "seq_shard",
                         "cache_shard", "vocab_shard", "scan_layers")]
    assert len(explicit) <= 1


@given(st.integers(0, 1000))
@settings(max_examples=50, deadline=None)
def test_normalize_idempotent(seed):
    """normalize(normalize(p)) == normalize(p), including for raw points
    whose inert factors were scrambled."""
    space = SearchSpace(ARCHS, SHAPES)
    rng = random.Random(seed)
    p = {k: rng.choice(v) for k, v in space.factors.items()}  # un-normalized
    q = space.normalize(p)
    assert space.normalize(q) == q


@given(st.integers(0, 1000))
@settings(max_examples=50, deadline=None)
def test_point_key_stable_under_renormalization(seed):
    """point_key is a function of the *normalized* point: scrambling inert
    factors or re-normalizing never changes identity."""
    space = SearchSpace(ARCHS, SHAPES)
    rng = random.Random(seed)
    p = space.random_point(rng)
    key = space.point_key(p)
    assert space.point_key(space.normalize(p)) == key
    scrambled = dict(p)
    if space.shapes[p["shape"]].kind != "train":
        scrambled["remat"] = rng.choice(space.factors["remat"])
        scrambled["n_microbatch"] = rng.choice(space.factors["n_microbatch"])
        assert space.point_key(scrambled) == key
    assert dict(key) == space.normalize(p)     # key round-trips to the point


@given(st.integers(0, 500), st.sampled_from(
    ["mesh", "preset", "optimizer", "n_microbatch", "attn_impl", "arch"]))
@settings(max_examples=40, deadline=None)
def test_restrict_never_widens_a_domain(seed, factor):
    space = SearchSpace(ARCHS, SHAPES)
    rng = random.Random(seed)
    dom = space.factors[factor]
    k = rng.randint(1, len(dom))
    allowed = rng.sample(list(dom), k)
    r = SearchSpace(ARCHS, SHAPES, restrict={factor: tuple(allowed)})
    assert set(r.factors[factor]) <= set(dom)
    assert set(r.factors[factor]) <= set(allowed)
    # junk restriction values can only narrow-to-nothing -> fall back whole
    r2 = SearchSpace(ARCHS, SHAPES, restrict={factor: ("no-such-value",)})
    assert set(r2.factors[factor]) == set(dom)
    for f in space.factors:
        if f != factor:
            assert r.factors[f] == space.factors[f]
    assert r.size() <= space.size()


def test_to_run_round_trip(space):
    rng = random.Random(3)
    p = space.random_point(rng)
    cfg, shape, policy, mesh_kind = space.to_run(p)
    assert cfg.name.startswith(p["arch"])
    assert shape.name == p["shape"]
    assert mesh_kind in ("single", "multi")
    assert policy.sharding_preset == p["preset"]
    assert not policy.use_pallas          # not a searched factor
    assert space.to_run({**p, "use_pallas": True})[2].use_pallas


def test_restriction(space):
    r = SearchSpace(ARCHS, SHAPES, restrict={"preset": ("tp",),
                                             "arch": ("rwkv6-7b",)})
    assert r.factors["preset"] == ("tp",)
    p = r.random_point(random.Random(0))
    assert p["preset"] == "tp" and p["arch"] == "rwkv6-7b"
    assert r.size() < space.size()
