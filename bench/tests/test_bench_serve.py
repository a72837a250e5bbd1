"""The serving cell's run at CPU size: a sound run is correct, and each
fault planted under the timed path, and the control, makes it incorrect.

These drive ``run.run_cell`` past its look for a chip, on the CPU, with a
tiny Qwen2-shaped configuration and the chat mix's shape
(``tests/data/``).
"""
import copy
import os

import jax
import jax.numpy as jnp
import pytest

import run as bench_run
from lib import common

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SEED = 2**33 + 12345          # above 32 bits, as the driver's seeds are


def files(**cell_changes):
    cell = common.load_json(DATA, "tiny-serve.json")
    cell.update(cell_changes)
    return {"name": "tiny", "entry": {"chips": 1}, "cell": cell,
            "config": common.load_json(DATA, "tiny-decoder.json"),
            "mix": common.load_json(DATA, "tiny-chat.json"),
            "end_to_end": [{"name": "ttft_p95_ms", "unit": "ms"},
                           {"name": "itl_p95_ms", "unit": "ms"},
                           {"name": "setup_s", "unit": "s"}],
            "per_layer": []}


def run_once(f, seed=SEED, seconds=1.0):
    clog = common.CompileLog()
    result, lines = bench_run.run_cell(f, seed, seconds, False,
                                       jax.devices()[:1], clog)
    assert lines[-3].startswith("check max_logit_gap")
    return result


def test_sound_run_is_correct():
    r = run_once(files())
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] == 40
    assert r["compiles_in_window"] == 0
    assert set(r["metrics"]) == {"ttft_p95_ms", "itl_p95_ms", "setup_s"}
    assert r["metrics"]["ttft_p95_ms"]["value"] > 0
    assert list(r)[-1] == "checks"


def _decode_keeps_state(cfg, policy):
    from repro.train.train_step import make_decode_step
    step = make_decode_step(cfg, policy)

    def broken(params, state, batch):
        logits, _ = step(params, state, batch)
        return logits, state
    return broken


def _decode_half_batch(cfg, policy):
    from repro.train.train_step import make_decode_step
    step = make_decode_step(cfg, policy)

    def broken(params, state, batch):
        logits, new = step(params, state, batch)
        half = logits.shape[0] // 2
        return jnp.concatenate([logits[:half], logits[:half]]), new
    return broken


def _token_altered(logits, key, temperature=0.0):
    return ((jnp.argmax(logits, -1) + 1) % logits.shape[-1]).astype(jnp.int32)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered"])
def test_fault_makes_run_incorrect(fault, monkeypatch):
    from repro.serve import engine
    if fault == "state_unchanged":
        monkeypatch.setattr(engine, "make_decode_step", _decode_keeps_state)
    elif fault == "half_batch":
        monkeypatch.setattr(engine, "make_decode_step", _decode_half_batch)
    else:
        monkeypatch.setattr(engine, "sample_logits", _token_altered)
    r = run_once(files())
    assert not r["correct"], r["checks"]
    assert r["checks"]["max_logit_gap"]["value"] > \
        r["checks"]["max_logit_gap"]["limit"]


def test_control_is_not_correct():
    """The reference in fp8 (the step below the bf16 the configuration
    states) put in the program's place fails the limit."""
    drv = common.driver("serve")
    f = files()
    ctx = bench_run.Context(f, SEED, 1.0, False, jax.devices()[:1],
                            common.CompileLog())
    out = drv.calibrate(ctx, control="fp8")
    limit = f["cell"]["correct"]["max_logit_gap"]
    assert out["program"]["max_logit_gap"] <= limit
    assert out["control"]["max_logit_gap"] > limit


def test_same_seed_same_work_other_seed_same_sizes():
    gen = common.generator("open_loop")
    mix = common.load_json(DATA, "tiny-chat.json")
    a = gen.schedule(mix, 20.0, 3.0, SEED, 512)
    b = gen.schedule(mix, 20.0, 3.0, SEED, 512)
    c = gen.schedule(mix, 20.0, 3.0, SEED + 1, 512)
    assert [(d, p.tolist(), m) for d, p, m in a] == \
        [(d, p.tolist(), m) for d, p, m in b]
    key = lambda s: (sorted(len(p) for _, p, _ in s),
                     sorted(m for _, _, m in s))
    assert key(a) == key(c) and a[-1][0] < 3.0
    assert copy.deepcopy(mix) == mix


def test_queue_wait_reader_takes_the_median_or_nothing():
    from types import SimpleNamespace
    reader = common.metric_reader("queue_wait_p50_ms.serve")
    read = lambda waits: reader.read(SimpleNamespace(
        out={"queue_wait_s": waits}))
    assert abs(read([0.03, 0.01, 0.02]) - 20.0) < 1e-9
    assert abs(read([0.04, 0.01, 0.02, 0.03]) - 25.0) < 1e-9
    assert read([]) is None
