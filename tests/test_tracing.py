"""The program's own tracing: spans and counters (``runtime/spans``), the
serving engine's spans, the named scopes of the model, train step and
optimizer in the lowered programs, and the compile log."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.all_archs import smoke_config
from repro.configs.base import RunPolicy
from repro.models import api
from repro.runtime import spans as spans_mod
from repro.runtime.spans import Spans
from repro.serve.engine import Request, ServingEngine
from repro.train.optimizer import OptConfig, init_opt_state
from repro.train.train_step import make_decode_step, make_train_step


def test_off_records_nothing_and_annotates_nothing(monkeypatch):
    made = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda *a, **k: made.append(a))
    sp = Spans()
    with sp("a", rid=1, x=2):
        with sp("b"):
            sp.mark("c")
            sp.count("n", 3)
    sp.tick()                          # not following the profiler: no-op
    assert sp.records == [] and dict(sp.counters) == {} and made == []
    assert sp("a") is sp("b")          # one shared null context
    f = lambda x: x + 1                # noqa: E731
    assert sp.wrap(f, "f") is f


def test_nested_spans_have_their_parent_and_request():
    sp = Spans(trace=True)
    with sp("step"):
        with sp("admit", rid=7, slot=2):
            with sp("prefill", len=5):
                pass
            sp.mark("tok")
        with sp("decode", active=3):
            pass
    names = [r.name for r in sp.records]
    assert names == ["step", "admit", "prefill", "tok", "decode"]
    step, admit, prefill, tok, decode = sp.records
    assert (step.parent, admit.parent, prefill.parent, tok.parent,
            decode.parent) == (None, 0, 1, 1, 0)
    assert (step.rid, admit.rid, prefill.rid, tok.rid, decode.rid) == (
        None, 7, 7, 7, None)
    assert admit.info == {"slot": 2} and prefill.info == {"len": 5}
    assert all(r.end_s is not None and r.end_s >= r.start_s
               for r in sp.records)
    assert step.start_s <= admit.start_s and admit.end_s <= decode.start_s
    assert tok.start_s <= tok.end_s <= admit.end_s


def test_counters_add_up():
    sp = Spans(trace=True)
    for n in (1, 2, 3):
        sp.count("lanes", n)
    sp.count("steps")
    assert dict(sp.counters) == {"lanes": 6, "steps": 1}


def test_wrap_spans_each_call():
    sp = Spans(trace=True)
    f = sp.wrap(lambda a, b: a + b, "add", lambda a, b: {"a": a})
    assert f(2, 3) == 5 and f(4, 1) == 5
    assert [(r.name, r.info) for r in sp.records] == [
        ("add", {"a": 2}), ("add", {"a": 4})]


def test_follows_the_profiler(tmp_path):
    """On while JAX's profiler records, with a clock mark at each tick;
    off again once it stops."""
    sp = Spans(follow_profiler=True)
    sp.tick()
    with sp("before"):
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        sp.tick()
        with sp("during"):
            pass
        sp.tick()
    finally:
        jax.profiler.stop_trace()
    sp.tick()
    with sp("after"):
        pass
    assert [r.name for r in sp.records] == [
        spans_mod.CLOCK, "during", spans_mod.CLOCK]
    assert sp.trace is False


@pytest.fixture(scope="module")
def tiny():
    cfg = smoke_config("qwen2-1.5b")
    params = api.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_engine_spans_every_request(tiny):
    cfg, params = tiny
    sp = Spans(trace=True)
    eng = ServingEngine(cfg, RunPolicy(), params, n_slots=2, cache_len=64,
                        spans=sp)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=10 + i, prompt=rng.integers(
        0, cfg.vocab_size, 5 + 3 * i).astype(np.int32), max_new_tokens=3)
        for i in range(5)]
    for r in reqs:
        eng.add_request(r)
    eng.run()
    recs = sp.records
    for r in reqs:
        mine = {x.name: x for x in recs if x.rid == r.rid}
        assert {"serve.enqueue", "serve.admit", "serve.prefill",
                "serve.slot_update", "serve.first_token"} <= set(mine)
        admit = mine["serve.admit"]
        assert recs[admit.parent].name == "serve.step"
        assert recs[mine["serve.first_token"].parent] is admit
        assert mine["serve.prefill"].info == {"len": len(r.prompt)}
        assert mine["serve.enqueue"].parent is None
        assert mine["serve.enqueue"].end_s <= admit.start_s
    steps = [x for x in recs if x.name == "serve.step"]
    phases = {"serve.upload", "serve.decode", "serve.sample", "serve.lanes"}
    for name in phases:
        inner = [x for x in recs if x.name == name]
        assert len(inner) == eng.stats["decode_steps"]
        assert all(recs[x.parent].name == "serve.step" for x in inner)
    assert len(steps) >= eng.stats["decode_steps"]
    decodes = [x for x in recs if x.name == "serve.decode"]
    assert sp.counters["serve.lanes_decoded"] == sum(
        x.info["active"] for x in decodes) == eng.stats["tokens_out"]
    assert sp.counters["serve.prompt_tokens"] == sum(
        len(r.prompt) for r in reqs)
    assert all(r.done and len(r.out) == 3 for r in reqs)


def test_engine_tracer_off_by_default(tiny):
    cfg, params = tiny
    eng = ServingEngine(cfg, RunPolicy(), params, n_slots=2, cache_len=64)
    assert eng.spans is spans_mod.PROGRAM and eng.spans.follow_profiler
    n = len(spans_mod.PROGRAM.records)
    eng.add_request(Request(rid=0, prompt=np.arange(4, dtype=np.int32),
                            max_new_tokens=2))
    eng.run()
    assert len(spans_mod.PROGRAM.records) == n


OP_NAME = re.compile(r'op_name="([^"]*)"')
TRANSFORM = re.compile(r"(?:transpose|jvp)\(|\)")


def _op_names(lowered):
    """Every op_name of the lowered HLO (debug info on), each between
    slashes and without the transforms around its scopes."""
    text = lowered.as_text(dialect="hlo", debug_info=True)
    return text, {"/" + TRANSFORM.sub("", n) + "/"
                  for n in OP_NAME.findall(text)}


def _has(names, scope):
    return any(f"/{scope}/" in n for n in names)


def test_decode_step_has_its_scopes(tiny):
    cfg, _ = tiny
    params = api.abstract_params(cfg, jnp.bfloat16)
    state = jax.eval_shape(lambda: api.init_state(cfg, 2, 16, jnp.bfloat16))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 1), jnp.int32),
             "position": jax.ShapeDtypeStruct((2,), jnp.int32)}
    _, names = _op_names(jax.jit(make_decode_step(cfg, RunPolicy())).lower(
        params, state, batch))
    for scope in ("embed", "layers", "attn/kv_cache", "mlp", "final_norm",
                  "unembed"):
        assert _has(names, scope), scope


def test_train_step_has_its_scopes(tiny):
    cfg, _ = tiny
    params = api.abstract_params(cfg)
    opt = OptConfig()
    opt_state = jax.eval_shape(lambda p: init_opt_state(opt, p), params)
    batch = {"tokens": jax.ShapeDtypeStruct((2, 16), jnp.int32),
             "labels": jax.ShapeDtypeStruct((2, 16), jnp.int32)}
    text, names = _op_names(jax.jit(make_train_step(
        cfg, RunPolicy(), opt)).lower(params, opt_state, batch))
    for scope in ("embed", "layers", "attn", "mlp", "final_norm", "unembed",
                  "loss", "optimizer"):
        assert _has(names, scope), scope
    assert "transpose(jvp(unembed))" in text     # the backward pass too


def test_compile_log_counts_by_program():
    """Two shapes, two compiles, under the function's own name."""
    log = spans_mod.CompileLog().install()
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        def twice_plus_one_for_the_log(x):
            return 2 * x + 1
        f = jax.jit(twice_plus_one_for_the_log)
        f(jnp.arange(3.0)).block_until_ready()
        f(jnp.arange(3.0)).block_until_ready()
        f(jnp.arange(4.0)).block_until_ready()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
    assert log.by_program["jit(twice_plus_one_for_the_log)"] == 2
    assert sum(log.by_program.values()) == log.programs >= 2
