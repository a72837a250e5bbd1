"""Deliverable (f): per-architecture smoke tests — REDUCED same-family config,
one forward + one train step on CPU, asserting output shapes + no NaNs."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import RunPolicy, ShapeSpec, get_config, list_archs
from repro.configs.all_archs import smoke_config
from repro.models import api
from repro.serve.engine import _update_slot
from repro.train.optimizer import OptConfig
from repro.train.train_step import (make_decode_step, make_init_opt,
                                    make_prefill_step, make_train_step)

ARCHS = list_archs()
SHAPE = ShapeSpec("smoke", "train", 32, 2)
POL = RunPolicy(remat="dots", n_microbatch=2, dtype="f32")


@pytest.fixture(scope="module")
def key():
    return jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_no_nans(arch, key):
    cfg = smoke_config(arch)
    params = api.init(cfg, key)
    batch = api.synthetic_batch(cfg, SHAPE, key)
    logits, aux = api.forward(params, batch, cfg, POL)
    B, S = 2, 32
    if cfg.frontend == "encodec":
        assert logits.shape == (B, S, cfg.n_codebooks, cfg.vocab_size)
    else:
        assert logits.shape == (B, S, cfg.vocab_size)
    assert not bool(jnp.isnan(logits).any())
    assert not bool(jnp.isnan(aux).any())


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step(arch, key):
    cfg = smoke_config(arch)
    params = api.init(cfg, key)
    opt = OptConfig(warmup=1, decay_steps=10)
    st = make_init_opt(cfg, POL, opt)(params)
    step = jax.jit(make_train_step(cfg, POL, opt))
    batch = api.synthetic_batch(cfg, SHAPE, key)
    params2, st2, m = step(params, st, batch)
    assert float(m["loss"]) > 0 and not jnp.isnan(m["loss"])
    assert float(m["grad_norm"]) > 0
    # params actually changed
    changed = jax.tree.leaves(jax.tree.map(
        lambda a, b: bool(jnp.any(a != b)), params, params2))
    assert any(changed)
    assert int(st2["step"]) == 1


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_consistency(arch, key):
    """Prefill(S-1) + decode(token S-1) must equal full forward at S-1."""
    cfg = smoke_config(arch)
    # dropless capacity: MoE token drops depend on grouping, which differs
    # between full-forward and prefill+decode; cf=E guarantees no drops so
    # the paths are comparable (drops themselves are tested in test_moe)
    pol = RunPolicy(remat="none", dtype="f32",
                    capacity_factor=float(max(cfg.n_experts, 1)))
    params = api.init(cfg, key)
    S, B = 24, 2
    batch = api.synthetic_batch(cfg, ShapeSpec("t", "train", S, B), key)
    tb = {k: v for k, v in batch.items() if k != "labels"}
    full_logits, _ = api.forward(params, tb, cfg, pol)
    pre = {k: (v[:, :v.shape[1] - 1] if k == "tokens" else v)
           for k, v in tb.items()}
    logits_p, state = make_prefill_step(cfg, pol, S + 4)(params, pre)
    err1 = float(jnp.max(jnp.abs(logits_p - full_logits[:, S - 2])))
    dbatch = {"tokens": tb["tokens"][:, -1:],
              "position": jnp.full((B,), S - 1, jnp.int32)}
    logits_d, _ = make_decode_step(cfg, pol)(params, state, dbatch)
    err2 = float(jnp.max(jnp.abs(logits_d - full_logits[:, S - 1])))
    scale = float(jnp.max(jnp.abs(full_logits))) + 1e-6
    assert err1 / scale < 1e-4, f"prefill mismatch {err1}"
    assert err2 / scale < 1e-4, f"decode mismatch {err2}"


@pytest.mark.parametrize("arch", ARCHS)
def test_multi_step_decode_matches_forward(arch, key):
    """Lanes prefilled at different lengths, written into one batched state
    by the engine's slot update, then decoded together for several steps
    (donating the state, as the engine does): each lane's logits at each
    position equal one full forward pass over that lane.  The window archs'
    attention rings (16 positions) wrap during decode."""
    cfg = smoke_config(arch)
    pol = RunPolicy(remat="none", dtype="f32",
                    capacity_factor=float(max(cfg.n_experts, 1)))
    params = api.init(cfg, key)
    lens, steps = (9, 13, 18), 6        # positions, image prefix included
    B, T = len(lens), max(lens) + steps
    # one forward over every lane's whole sequence; causal, so a lane's
    # logits at a position see only what decode has seen there
    tb = api.synthetic_batch(cfg, ShapeSpec("t", "train", T, B), key)
    tb.pop("labels")
    full = jax.jit(lambda p, b: api.forward(p, b, cfg, pol)[0])(params, tb)
    # a window arch's attention state is a ring of min(T, window) rows
    prefill = jax.jit(make_prefill_step(
        cfg, pol, min(T, cfg.window) if cfg.window else T))
    decode = jax.jit(make_decode_step(cfg, pol), donate_argnums=1)
    off = cfg.n_prefix if cfg.frontend == "vit" else 0
    state = api.init_state(cfg, B, T, jnp.float32)
    for b, S in enumerate(lens):
        lane = {k: v[b:b + 1] for k, v in tb.items()}
        lane["tokens"] = lane["tokens"][:, :S - off]
        _, state1 = prefill(params, lane)
        state = _update_slot(state, state1, b)
    scale = float(jnp.max(jnp.abs(full))) + 1e-6
    for j in range(steps):
        pos = jnp.asarray([S + j for S in lens], jnp.int32)
        toks = jnp.stack([tb["tokens"][b, S - off + j]
                          for b, S in enumerate(lens)])[:, None]
        logits, state = decode(params, state,
                               {"tokens": toks, "position": pos})
        err = float(jnp.max(jnp.abs(logits - full[jnp.arange(B), pos])))
        assert err / scale < 1e-4, (j, err)


def test_full_configs_exact_dims():
    """The FULL configs carry the exact assignment dims (no allocation)."""
    checks = {
        "qwen2-1.5b": dict(n_layers=28, d_model=1536, n_heads=12,
                           n_kv_heads=2, d_ff=8960, vocab_size=151936,
                           qkv_bias=True),
        "deepseek-67b": dict(n_layers=95, d_model=8192, n_heads=64,
                             n_kv_heads=8, d_ff=22016, vocab_size=102400),
        "mixtral-8x7b": dict(n_experts=8, top_k=2, window=4096),
        "phi3.5-moe-42b-a6.6b": dict(n_experts=16, top_k=2, d_ff=6400),
        "recurrentgemma-2b": dict(block_pattern=("rec", "rec", "attn"),
                                  vocab_size=256000, window=2048),
        "rwkv6-7b": dict(block_pattern=("rwkv",), vocab_size=65536),
        "musicgen-medium": dict(n_codebooks=4, vocab_size=2048, n_heads=24,
                                n_kv_heads=24),
        "internvl2-1b": dict(frontend="vit", vocab_size=151655),
    }
    for arch, kv in checks.items():
        cfg = get_config(arch)
        for k, v in kv.items():
            assert getattr(cfg, k) == v, (arch, k, getattr(cfg, k), v)


def test_param_counts_match_nominal():
    nominal = {"qwen2-1.5b": 1.54e9, "tinyllama-1.1b": 1.10e9,
               "internlm2-20b": 19.9e9, "deepseek-67b": 67e9,
               "mixtral-8x7b": 46.7e9, "phi3.5-moe-42b-a6.6b": 41.9e9,
               "recurrentgemma-2b": 2.7e9, "rwkv6-7b": 7.6e9}
    for arch, nom in nominal.items():
        n = api.n_params(get_config(arch))
        assert 0.93 < n / nom < 1.07, (arch, n, nom)
