"""End-to-end behaviour tests: training convergence, checkpoint-resume
determinism, serving, data pipeline."""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.ckpt.checkpoint import CheckpointManager
from repro.configs.base import RunPolicy, ShapeSpec
from repro.configs.all_archs import smoke_config
from repro.data.pipeline import Prefetcher, SyntheticLM
from repro.models import api
from repro.serve.engine import Request, ServingEngine
from repro.train.optimizer import OptConfig
from repro.train.train_step import make_init_opt, make_train_step

CFG = smoke_config("tinyllama-1.1b")
SHAPE = ShapeSpec("sys", "train", 64, 8)
POL = RunPolicy(remat="none", dtype="f32", n_microbatch=2)
OPT = OptConfig(lr=3e-3, warmup=5, decay_steps=200)


def _train(n_steps, params, st, step_fn, pipe, start=0):
    losses = []
    for i in range(start, start + n_steps):
        batch = {k: jnp.asarray(v) for k, v in pipe.batch(i).items()}
        params, st, m = step_fn(params, st, batch)
        losses.append(float(m["loss"]))
    return params, st, losses


def test_training_learns_synthetic_structure():
    """Loss on the bigram-structured corpus drops well below ln(vocab)."""
    pipe = SyntheticLM(CFG, SHAPE, seed=0)
    params = api.init(CFG, jax.random.PRNGKey(0))
    st = make_init_opt(CFG, POL, OPT)(params)
    step = jax.jit(make_train_step(CFG, POL, OPT))
    params, st, losses = _train(40, params, st, step, pipe)
    assert losses[-1] < losses[0] - 0.5, (losses[0], losses[-1])


def test_checkpoint_resume_bit_exact(tmp_path):
    """train 12 == train 8 + save + restore + train 4 (same data order)."""
    pipe = SyntheticLM(CFG, SHAPE, seed=1)
    step = jax.jit(make_train_step(CFG, POL, OPT))
    params = api.init(CFG, jax.random.PRNGKey(0))
    st = make_init_opt(CFG, POL, OPT)(params)
    pA, sA, _ = _train(12, params, st, step, pipe)

    pB, sB, _ = _train(8, params, st, step, pipe)
    cm = CheckpointManager(str(tmp_path), async_write=False)
    cm.save(8, {"params": pB, "opt": sB})
    meta, restored = cm.restore_latest({"params": pB, "opt": sB})
    assert meta["step"] == 8
    pC, sC, _ = _train(4, restored["params"], restored["opt"], step, pipe,
                       start=8)
    for a, c in zip(jax.tree.leaves(pA), jax.tree.leaves(pC)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_data_pipeline_determinism_and_host_sharding():
    p0 = SyntheticLM(CFG, SHAPE, seed=3, host_index=0, n_hosts=2)
    p0b = SyntheticLM(CFG, SHAPE, seed=3, host_index=0, n_hosts=2)
    p1 = SyntheticLM(CFG, SHAPE, seed=3, host_index=1, n_hosts=2)
    b0, b0b, b1 = p0.batch(5), p0b.batch(5), p1.batch(5)
    np.testing.assert_array_equal(b0["tokens"], b0b["tokens"])  # deterministic
    assert not np.array_equal(b0["tokens"], b1["tokens"])       # disjoint
    assert b0["tokens"].shape[0] == SHAPE.global_batch // 2
    # labels are next-token shifted
    np.testing.assert_array_equal(b0["tokens"][:, 1:],
                                  b0["labels"][:, :-1])


def test_prefetcher():
    pipe = SyntheticLM(CFG, SHAPE, seed=0)
    pf = Prefetcher(pipe, start_step=3, depth=2)
    try:
        s, b = pf.next()
        assert s == 3
        s2, b2 = pf.next()
        assert s2 == 4
        np.testing.assert_array_equal(b["tokens"], pipe.batch(3)["tokens"])
    finally:
        pf.close()


def test_serving_engine_completes_requests():
    cfg = smoke_config("qwen2-1.5b")
    pol = RunPolicy(remat="none", dtype="f32")
    params = api.init(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, pol, params, n_slots=3, cache_len=48)
    for i in range(6):
        eng.add_request(Request(rid=i, prompt=np.arange(8, dtype=np.int32),
                                max_new_tokens=5))
    done = eng.run()
    assert len(done) == 6
    assert all(len(r.out) == 5 for r in done)
    assert eng.stats["prefills"] == 6
    assert eng.stats["decode_steps"] >= 2


def test_serving_greedy_matches_decode_path():
    """Greedy serve output == argmax over sequential full forwards."""
    cfg = smoke_config("qwen2-1.5b")
    pol = RunPolicy(remat="none", dtype="f32")
    params = api.init(cfg, jax.random.PRNGKey(0))
    prompt = np.arange(6, dtype=np.int32)
    eng = ServingEngine(cfg, pol, params, n_slots=1, cache_len=32)
    eng.add_request(Request(rid=0, prompt=prompt, max_new_tokens=4))
    out = eng.run()[0].out
    toks = list(prompt)
    ref = []
    for _ in range(4):
        logits, _ = api.forward(params, {"tokens": jnp.asarray([toks])},
                                cfg, pol)
        t = int(jnp.argmax(logits[0, -1]))
        ref.append(t)
        toks.append(t)
    assert out == ref, (out, ref)


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "mixtral-8x7b",
                                  "recurrentgemma-2b", "rwkv6-7b"])
def test_serving_logits_match_full_forward(arch):
    """Logits a request collects (prefill, then cache decode on a shared
    batch) equal one full forward pass over prompt plus output.  Every block
    kind: attention (MoE and a 16-position window in mixtral, whose ring the
    longest prompt overruns and decode wraps), RG-LRU and RWKV."""
    cfg = smoke_config(arch)
    # dropless capacity: MoE token drops depend on grouping, which differs
    # between the full forward and prefill + decode
    pol = RunPolicy(remat="none", dtype="f32",
                    capacity_factor=float(max(cfg.n_experts, 1)))
    params = api.init(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, pol, params, n_slots=2, cache_len=32)
    reqs = [Request(rid=i, prompt=np.arange(3 + 8 * i, dtype=np.int32),
                    max_new_tokens=5) for i in range(3)]
    reqs[1].logits, reqs[2].logits = [], []
    for r in reqs:
        eng.add_request(r)
    eng.run()
    for r in reqs[1:]:
        toks = np.concatenate([r.prompt, np.asarray(r.out[:-1], np.int32)])
        logits, _ = api.forward(params, {"tokens": jnp.asarray([toks])}, cfg,
                                pol)
        want = np.asarray(logits[0, len(r.prompt) - 1:])
        np.testing.assert_allclose(np.stack(r.logits), want, atol=1e-4)
    assert reqs[0].logits is None


def test_serving_decode_donates_its_state():
    """Decode takes the state donated: after a step the previous state's
    buffers are gone (each layer's cache written in place), or, on a
    backend that does not donate, the program marks every leaf donated."""
    cfg = smoke_config("qwen2-1.5b")
    pol = RunPolicy(remat="none", dtype="f32")
    params = api.init(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, pol, params, n_slots=2, cache_len=32)
    eng.add_request(Request(rid=0, prompt=np.arange(5, dtype=np.int32),
                            max_new_tokens=4))
    eng.step()
    old = jax.tree.leaves(eng.state)
    eng.step()
    assert len(eng.completed) == 0 and eng.stats["decode_steps"] == 2
    if not all(a.is_deleted() for a in old):
        batch = {"tokens": jnp.zeros((2, 1), jnp.int32),
                 "position": jnp.zeros((2,), jnp.int32)}
        text = eng.decode.lower(eng.params, eng.state, batch).as_text()
        assert text.count("tf.aliasing_output") == len(old)


def test_serving_slot_update_is_one_program():
    """Admission writes any lane with one program: the slot is traced, so
    set-up does not load a program a lane."""
    cfg = smoke_config("qwen2-1.5b")
    pol = RunPolicy(remat="none", dtype="f32")
    params = api.init(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, pol, params, n_slots=3, cache_len=32)
    before = eng._update._cache_size()      # the jit cache is per process
    for i in range(3):
        eng.add_request(Request(rid=i, prompt=np.arange(4, dtype=np.int32),
                                max_new_tokens=4))
    eng.step()                      # admits all three, one to each lane
    assert all(r is not None for r in eng.slot_req)
    assert eng._update._cache_size() - before <= 1


def test_launcher_trains_checkpoints_and_resumes_on_request(tmp_path,
                                                            monkeypatch):
    """launch/train.main: finite losses, a checkpoint in --ckpt-dir, a
    fresh start unless --resume, and --resume continues the step count."""
    from repro.launch import train
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    ck = str(tmp_path / "ck")
    argv = ["--smoke", "--steps", "2", "--seq", "16", "--batch", "2",
            "--ckpt-dir", ck, "--ckpt-every", "2"]
    first = train.main(argv)
    assert len(first["losses"]) == 2
    assert all(np.isfinite(first["losses"]))
    assert CheckpointManager(ck).list_steps() == [2]
    again = train.main(argv)
    assert again["losses"] == first["losses"]
    train.main(argv + ["--resume"])
    assert CheckpointManager(ck).list_steps() == [2, 4]


def test_compile_cache_dir_is_fixed_or_the_env_var(monkeypatch, tmp_path):
    from repro.launch import compile_cache
    prev = jax.config.jax_compilation_cache_dir
    prev_meta = jax.config.jax_compilation_cache_include_metadata_in_key
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == prev
        # scope names are part of the key, so a cached program keeps its own
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert compile_cache.enable() == compile_cache.CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == compile_cache.CACHE_DIR
        assert compile_cache.CACHE_DIR == os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          prev_meta)
