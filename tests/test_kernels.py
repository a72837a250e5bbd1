"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import flash_decode
from repro.kernels.flash_attention import flash_attention, flash_attention_fwd
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.rwkv6_kernel import rwkv6_wkv


def rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("B,H,KVH,Sq,Skv,D", [
    (1, 2, 2, 16, 16, 16),      # MHA, tiny
    (2, 4, 2, 48, 48, 32),      # GQA, non-block-multiple seq
    (1, 6, 2, 128, 128, 64),    # GQA 3:1
    (2, 2, 1, 33, 65, 32),      # MQA, ragged sizes
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [None, 24])
def test_flash_attention_fwd(B, H, KVH, Sq, Skv, D, dtype, window):
    key = jax.random.PRNGKey(0)
    q = rand(key, (B, H, Sq, D), dtype)
    k = rand(jax.random.fold_in(key, 1), (B, KVH, Skv, D), dtype)
    v = rand(jax.random.fold_in(key, 2), (B, KVH, Skv, D), dtype)
    shift = Skv - Sq
    o, _ = flash_attention_fwd(q, k, v, window=window, causal_shift=shift,
                               block_q=16, block_k=16, interpret=True)
    r = ref.flash_attention_ref(q, k, v, window=window, causal_shift=shift)
    err = float(jnp.max(jnp.abs(o.astype(jnp.float32) - r.astype(jnp.float32))))
    assert err < TOL[dtype], err


@pytest.mark.parametrize("window", [None, 20])
def test_flash_attention_grads(window):
    B, H, KVH, S, D = 2, 4, 2, 48, 32
    key = jax.random.PRNGKey(3)
    q = rand(key, (B, H, S, D), jnp.float32)
    k = rand(jax.random.fold_in(key, 1), (B, KVH, S, D), jnp.float32)
    v = rand(jax.random.fold_in(key, 2), (B, KVH, S, D), jnp.float32)
    w = rand(jax.random.fold_in(key, 3), (B, H, S, D), jnp.float32)

    def f_ker(q, k, v):
        return (flash_attention(q, k, v, window, 0, 16, 16, True) * w).sum()

    def f_ref(q, k, v):
        return (ref.flash_attention_ref(q, k, v, window=window) * w).sum()

    gk = jax.grad(f_ker, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gk, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("B,H,KVH,T,D", [(2, 4, 2, 100, 32), (1, 2, 1, 64, 64),
                                         (3, 3, 3, 40, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [None, 30])
def test_flash_decode(B, H, KVH, T, D, dtype, window):
    key = jax.random.PRNGKey(1)
    q = rand(key, (B, H, D), dtype)
    k = rand(jax.random.fold_in(key, 1), (B, KVH, T, D), dtype)
    v = rand(jax.random.fold_in(key, 2), (B, KVH, T, D), dtype)
    pos = jnp.broadcast_to(jnp.arange(T), (B, T)).astype(jnp.int32)
    pos = pos.at[:, T - 10:].set(-1)            # unwritten ring slots
    qpos = jnp.array([T - 11] + [T // 2] * (B - 1), jnp.int32)
    o = flash_decode(q, k, v, pos, qpos, window=window, block_k=16,
                     interpret=True)
    r = ref.flash_decode_ref(q, k, v, pos, qpos, window=window)
    err = float(jnp.max(jnp.abs(o.astype(jnp.float32) - r.astype(jnp.float32))))
    assert err < TOL[dtype], err


@pytest.mark.parametrize("B,S,W", [(2, 50, 64), (1, 256, 128), (3, 17, 32)])
def test_rglru_scan(B, S, W):
    key = jax.random.PRNGKey(2)
    a = jax.random.uniform(key, (B, S, W), jnp.float32, 0.5, 0.999)
    b = rand(jax.random.fold_in(key, 1), (B, S, W), jnp.float32)
    o = rglru_scan(a, b, block_s=16, interpret=True)
    r = ref.rglru_scan_ref(a, b)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=1e-5)


@pytest.mark.parametrize("B,H,S,hs", [(2, 3, 70, 16), (1, 2, 64, 32),
                                      (1, 1, 130, 64)])
@pytest.mark.parametrize("chunk", [16, 32])
def test_rwkv6_wkv(B, H, S, hs, chunk):
    key = jax.random.PRNGKey(4)
    r = rand(key, (B, H, S, hs), jnp.float32)
    k = rand(jax.random.fold_in(key, 1), (B, H, S, hs), jnp.float32)
    v = rand(jax.random.fold_in(key, 2), (B, H, S, hs), jnp.float32)
    w_log = -jnp.exp(rand(jax.random.fold_in(key, 3), (B, H, S, hs),
                          jnp.float32))
    u = rand(jax.random.fold_in(key, 5), (H, hs), jnp.float32)
    o = rwkv6_wkv(r, k, v, w_log, u, chunk=chunk, interpret=True)
    rr = ref.rwkv6_wkv_ref(r, k, v, w_log, u)
    scale = float(jnp.max(jnp.abs(rr))) + 1e-9
    err = float(jnp.max(jnp.abs(o - rr))) / scale
    assert err < 1e-5, err


def test_blocked_attention_matches_plain():
    """The model's online-softmax path == materialized-score path."""
    from repro.models.attention import blocked_attention, plain_attention
    key = jax.random.PRNGKey(7)
    B, S, KV, G, dh = 2, 65, 2, 3, 16
    q = rand(key, (B, S, KV, G, dh), jnp.float32)
    k = rand(jax.random.fold_in(key, 1), (B, S, KV, dh), jnp.float32)
    v = rand(jax.random.fold_in(key, 2), (B, S, KV, dh), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    w = rand(jax.random.fold_in(key, 3), (B, S, KV, G, dh), jnp.float32)
    for win in (None, 20):
        a = blocked_attention(q, k, v, pos, pos, window=win, block=16)
        b = plain_attention(q, k, v, pos, pos, window=win)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
        ga, gb = (jax.grad(lambda q, k, v: (fn(q, k, v, pos, pos, window=win,
                                                **kw) * w).sum(),
                           argnums=(0, 1, 2))(q, k, v)
                  for fn, kw in ((blocked_attention, {"block": 16}),
                                 (plain_attention, {})))
        for x, y in zip(ga, gb):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       atol=1e-4)


def test_local_chunk_attention_exact_window():
    from repro.models.attention import local_chunk_attention, plain_attention
    key = jax.random.PRNGKey(8)
    B, S, KV, G, dh, W = 1, 100, 1, 2, 16, 16
    q = rand(key, (B, S, KV, G, dh), jnp.float32)
    k = rand(jax.random.fold_in(key, 1), (B, S, KV, dh), jnp.float32)
    v = rand(jax.random.fold_in(key, 2), (B, S, KV, dh), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    a = local_chunk_attention(q, k, v, pos, pos, window=W)
    b = plain_attention(q, k, v, pos, pos, window=W)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def _op_args(name):
    key = jax.random.PRNGKey(9)
    if name == "attention":
        return (rand(key, (1, 4, 32, 16), jnp.float32),
                rand(key, (1, 2, 32, 16), jnp.float32),
                rand(key, (1, 2, 32, 16), jnp.float32)), {"block_q": 16,
                                                          "block_k": 16}
    if name == "decode_attention":
        pos = jnp.broadcast_to(jnp.arange(32, dtype=jnp.int32), (2, 32))
        return (rand(key, (2, 4, 16), jnp.float32),
                rand(key, (2, 2, 32, 16), jnp.float32),
                rand(key, (2, 2, 32, 16), jnp.float32), pos,
                jnp.array([31, 9], jnp.int32)), {"block_k": 16}
    if name == "rglru":
        return (jax.random.uniform(key, (1, 32, 16), jnp.float32, 0.5, 0.9),
                rand(key, (1, 32, 16), jnp.float32)), {"block_s": 16}
    return (rand(key, (1, 2, 32, 16), jnp.float32),
            rand(key, (1, 2, 32, 16), jnp.float32),
            rand(key, (1, 2, 32, 16), jnp.float32),
            -jnp.exp(rand(key, (1, 2, 32, 16), jnp.float32)),
            rand(key, (2, 16), jnp.float32)), {"chunk": 16}


@pytest.mark.parametrize("name", ["attention", "decode_attention", "rglru",
                                  "rwkv6"])
def test_ops_interpret_only_on_request(name):
    """Off the TPU a kernel runs only when interpret mode is asked for;
    without the request it raises instead of falling back."""
    from repro.kernels import ops
    fn = getattr(ops, name)
    args, kw = _op_args(name)
    want = fn(*args, use_pallas=False)
    got = fn(*args, use_pallas=True, interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)
    with pytest.raises(ValueError, match="interpret"):
        fn(*args, use_pallas=True, **kw)


def test_prefill_with_cache_takes_the_flash_kernel(monkeypatch):
    """Prefill that builds the cache routes use_pallas attention through the
    flash kernel (interpret mode here) and agrees with the plain path; off
    the TPU without interpret it raises rather than running plain."""
    from repro.configs.all_archs import smoke_config
    from repro.configs.base import RunPolicy
    from repro.kernels import ops
    from repro.models import api
    from repro.train.train_step import make_prefill_step
    cfg = smoke_config("qwen2-1.5b")
    params = api.init(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0,
                                          cfg.vocab_size, jnp.int32)}
    f32 = dict(dtype="f32", remat="none")
    want = make_prefill_step(cfg, RunPolicy(**f32), 32)(params, batch)
    kernel = make_prefill_step(cfg, RunPolicy(use_pallas=True, **f32), 32)
    with pytest.raises(ValueError, match="interpret"):
        kernel(params, batch)
    calls = []
    attention = ops.attention

    def interpreted(*a, **kw):
        calls.append(1)
        return attention(*a, interpret=True, **kw)
    monkeypatch.setattr(ops, "attention", interpreted)
    got = kernel(params, batch)
    assert calls
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-4)
