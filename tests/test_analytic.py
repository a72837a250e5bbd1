"""Sanity of the analytic cost model ("the spec")."""
import pytest
from repro.launch.mesh import make_abstract_mesh

from repro.configs.base import SHAPES, RunPolicy, get_config
from repro import hw
from repro.core import analytic

MESH = make_abstract_mesh((16, 16), ("data", "model"))
MESH3 = make_abstract_mesh((2, 16, 16), ("pod", "data", "model"))


def test_model_flops_train_is_6nd():
    cfg = get_config("tinyllama-1.1b")
    shape = SHAPES["train_4k"]
    from repro.models import api
    expect = 6.0 * api.n_params(cfg) * 4096 * 256
    assert abs(analytic.model_flops(cfg, shape) - expect) / expect < 1e-9


def test_moe_uses_active_params():
    cfg = get_config("mixtral-8x7b")
    shape = SHAPES["train_4k"]
    from repro.models import api
    fl = analytic.model_flops(cfg, shape)
    dense_fl = 6.0 * api.n_params(cfg) * 4096 * 256
    assert fl < 0.4 * dense_fl                  # active 12.9B of 46.7B


def test_attention_flops_windowed_smaller():
    full = get_config("deepseek-67b")
    win = get_config("mixtral-8x7b")
    s = SHAPES["prefill_32k"]
    af = analytic.attention_flops(full, s)
    aw = analytic.attention_flops(win, s)
    # mixtral window 4096 << 32768 quadratic
    per_head_full = af / (full.n_layers * full.n_heads * full.d_head)
    per_head_win = aw / (win.n_layers * win.n_heads * win.d_head)
    assert per_head_win < 0.3 * per_head_full


def test_decode_flops_per_token():
    cfg = get_config("qwen2-1.5b")
    s = SHAPES["decode_32k"]
    fl = analytic.model_flops(cfg, s)
    from repro.models import api
    assert abs(fl - 2.0 * api.n_active_params(cfg) * 128) / fl < 1e-9


def test_floors_positive_and_ordered():
    cfg = get_config("deepseek-67b")
    pol = RunPolicy(sharding_preset="tp", remat="full", n_microbatch=8)
    f = analytic.step_floor_seconds(cfg, SHAPES["train_4k"], pol, MESH,
                                     hw.V5E)
    assert f["compute_s"] > 0 and f["memory_s"] > 0
    assert f["floor_s"] >= max(f["compute_s"], f["memory_s"],
                               f["collective_s"]) - 1e-12


def test_compression_lowers_collective_floor():
    cfg = get_config("tinyllama-1.1b")
    base = RunPolicy(sharding_preset="dp", grad_compress="none")
    comp = RunPolicy(sharding_preset="dp", grad_compress="int8")
    a = analytic.collective_floor_bytes(cfg, SHAPES["train_4k"], base, MESH3)
    b = analytic.collective_floor_bytes(cfg, SHAPES["train_4k"], comp, MESH3)
    assert b < a


def test_matmul_params_excludes_input_embedding():
    from repro.models import api
    cfg = get_config("tinyllama-1.1b")        # untied
    n_all = api.n_params(cfg)
    n_mm = api.matmul_active_params(cfg)
    embed = cfg.vocab_size * cfg.d_model
    assert n_mm < n_all
    assert abs((n_all - n_mm) - embed) / embed < 0.2


def test_chip_table_by_device_kind():
    assert hw.chip_spec("TPU v5 lite").peak_flops_bf16 == 197e12
    with pytest.raises(ValueError, match="no peaks"):
        hw.chip_spec("TPU v99")
    # host CPU meshes and stand-ins model the v5e
    assert hw.chip_of_meshes({"single": object()}) is hw.V5E
    assert hw.chip_of_meshes({"single": make_abstract_mesh((1,), ("x",))}) \
        is hw.V5E
