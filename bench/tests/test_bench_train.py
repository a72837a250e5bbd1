"""The training cell's run at CPU size: a sound run is correct; a step that
returns its state unchanged, a step that sees half of its batch, and the
control (the reference in fp8 in the program's place) are not.
"""
import os

import jax
import pytest

import run as bench_run
from lib import common

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SEED = 2**33 + 7


def files():
    return {"name": "tiny", "entry": {"chips": 1},
            "cell": common.load_json(DATA, "tiny-train.json"),
            "config": common.load_json(DATA, "tiny-vlm.json"),
            "mix": common.load_json(DATA, "tiny-rows.json"),
            "end_to_end": [{"name": "train_tokens_per_s", "unit": "tokens/s"},
                           {"name": "setup_s", "unit": "s"}],
            "per_layer": []}


def test_sound_run_is_correct():
    result, lines = bench_run.run_cell(files(), SEED, 1.0, False,
                                       jax.devices()[:1], common.CompileLog())
    assert result["correct"], result["checks"]
    assert result["compiles_in_window"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert [l.split()[1] for l in lines[-3:]] == [
        "loss_gap", "grad_norm_gap", "change_norm_gap"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_fault_makes_run_incorrect(fault, monkeypatch):
    drv = common.driver("train")
    setup = drv.setup
    monkeypatch.setattr(drv, "setup",
                        lambda ctx: setup(ctx, fault=drv.FAULTS[fault]))
    result, _ = bench_run.run_cell(files(), SEED, 0.5, False,
                                   jax.devices()[:1], common.CompileLog())
    assert not result["correct"], result["checks"]


def test_control_is_not_correct():
    f = files()
    ctx = bench_run.Context(f, SEED, 0.5, False, jax.devices()[:1],
                            common.CompileLog())
    out = common.driver("train").calibrate(ctx, control="fp8")
    limits = f["cell"]["correct"]
    assert all(out["program"][k] <= lim for k, lim in limits.items())
    assert any(out["control"][k] > lim for k, lim in limits.items())
