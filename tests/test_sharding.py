"""Sharding-rule resolution with hypothesis property tests (AbstractMesh —
no devices needed for spec resolution)."""
import jax
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_abstract_mesh
import pytest
from hypothesis import given, settings, strategies as st

from repro.launch.sharding import PRESETS, make_rules, spec_for

MESH = make_abstract_mesh((16, 16), ("data", "model"))
MESH3 = make_abstract_mesh((2, 16, 16), ("pod", "data", "model"))


def test_divisible_dim_shards():
    spec = spec_for((8960, 1536), ("mlp", "embed"), PRESETS["tp"], MESH)
    assert spec[0] == "model"


def test_indivisible_falls_back():
    # 12 heads on a 16-way model axis -> replicate
    spec = spec_for((1536, 12, 128), ("embed", "heads", "head_dim"),
                    PRESETS["tp"], MESH)
    assert spec == P(None, None, None)


def test_axis_not_reused_within_tensor():
    rules = make_rules("tp", embed=[("model",)])
    spec = spec_for((1536, 16384), ("embed", "mlp"), rules, MESH)
    used = [s for s in spec if s is not None]
    assert used == ["model"]                     # embed wins, mlp skipped


def test_multi_axis_candidate():
    spec = spec_for((256, 4096), ("batch", None), PRESETS["fsdp"], MESH3)
    assert spec[0] == ("pod", "data")


def test_missing_axis_candidate_skipped():
    # ("pod","data") unavailable on the 2D mesh -> ("data",)
    spec = spec_for((256, 4096), ("batch", None), PRESETS["fsdp"], MESH)
    assert spec[0] == "data"


def test_batch_of_one_replicates():
    spec = spec_for((1, 4096), ("batch", None), PRESETS["fsdp"], MESH)
    assert spec == P(None, None)


@st.composite
def shapes_axes(draw):
    names = ["embed", "mlp", "heads", "kv_heads", "vocab", "batch",
             "expert", None]
    n = draw(st.integers(1, 4))
    axes = tuple(draw(st.sampled_from(names)) for _ in range(n))
    shape = tuple(draw(st.sampled_from([1, 2, 3, 8, 12, 16, 32, 256, 8960]))
                  for _ in range(n))
    return shape, axes


@given(shapes_axes(), st.sampled_from(list(PRESETS)))
@settings(max_examples=200, deadline=None)
def test_spec_always_valid(sa, preset):
    """Invariants: no mesh axis used twice; every sharded dim divisible."""
    shape, axes = sa
    spec = spec_for(shape, axes, PRESETS[preset], MESH3)
    used = []
    for dim, part in zip(shape, spec):
        if part is None:
            continue
        parts = (part,) if isinstance(part, str) else tuple(part)
        total = 1
        for m in parts:
            assert m in MESH3.shape
            total *= MESH3.shape[m]
        assert dim % total == 0
        used.extend(parts)
    assert len(used) == len(set(used))


@given(st.sampled_from(["qwen2-1.5b", "deepseek-67b", "mixtral-8x7b",
                        "rwkv6-7b", "recurrentgemma-2b"]),
       st.sampled_from(list(PRESETS)))
@settings(max_examples=40, deadline=None)
def test_param_tree_specs_resolve(arch, preset):
    """Every param of every arch gets a valid PartitionSpec on both meshes."""
    from repro.configs.base import get_config
    from repro.models import api
    cfg = get_config(arch)
    shapes = api.abstract_params(cfg)
    axes = api.axes(cfg)

    def walk(s, a):
        if isinstance(s, dict):
            for k in s:
                walk(s[k], a[k])
            return
        for mesh in (MESH, MESH3):
            spec = spec_for(s.shape, a, PRESETS[preset], mesh)
            assert len(spec) == len(s.shape)
    walk(shapes, axes)
