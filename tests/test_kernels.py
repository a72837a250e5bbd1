"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import hw
from repro.kernels import ref
from repro.kernels.decode_attention import flash_decode
from repro.kernels import flash_attention as fa
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


@pytest.mark.parametrize("S", [128, 256, 384, 512, 1000, 1024, 1100, 1536,
                               2048, 33, 100, 2047])
def test_choose_blocks(S):
    """Blocks tile the length evenly in 128-row multiples (16 below 128),
    as few as the largest blocks allow, padding by less than a tile a
    block; at every head dim the models use (up to 256) the kernels' VMEM
    stays within a quarter of the chip's."""
    for sq, skv in ((S, S), (S, 2048), (2048, S)):
        bq, bk = fa.choose_blocks(sq, skv)
        for b, n, most in ((bq, sq, fa.BLOCK_Q), (bk, skv, fa.BLOCK_K)):
            unit = 128 if n > 128 else 16
            assert b % unit == 0 and 0 < b <= max(most, unit)
            blocks = -(-n // b)
            assert blocks == -(-n // most)
            assert blocks * b - n < blocks * unit
        for d in (64, 128, 256):
            assert fa.vmem_bytes(bq, bk, d, 4) <= hw.V5E.vmem_bytes / 4


def test_choose_blocks_cells():
    """The benchmark cells' shapes (qwen2-1.5b's 2048 prefill, internvl2-1b's
    2048 training rows) take the largest blocks; shorter and ragged lengths
    split evenly."""
    assert fa.choose_blocks(2048, 2048) == (fa.BLOCK_Q, fa.BLOCK_K) == (512, 1024)
    assert fa.choose_blocks(128, 128) == (128, 128)
    assert fa.choose_blocks(1536, 1536) == (512, 768)
    assert fa.choose_blocks(1100, 1300) == (384, 768)


def test_vmem_limit_follows_the_blocks():
    """A scoped VMEM limit is asked for only past Mosaic's default, and then
    from the blocks' own need."""
    assert fa._compiler_params(128, 128, 128, 2) is None
    need = fa.vmem_bytes(fa.BLOCK_Q, fa.BLOCK_K, 128)
    assert need > fa.SCOPED_VMEM
    limit = fa._compiler_params(fa.BLOCK_Q, fa.BLOCK_K, 128, 2).vmem_limit_bytes
    assert need <= limit <= 2 * need


def _fetches(indexes):
    """Block copies a grid makes: one each time the index changes."""
    return [b for i, b in enumerate(indexes) if i == 0 or b != indexes[i - 1]]


GEOMS = [  # Sq, Skv, bq, bk, window, causal_shift
    (2048, 2048, 1024, 512, None, 0),
    (2048, 2048, 512, 512, None, 0),
    (1536, 1536, 512, 512, 600, 0),
    (1100, 1300, 256, 512, 300, 200),
    (48, 48, 16, 16, 24, 0),
    (33, 65, 16, 16, None, 32),
]


@pytest.mark.parametrize("sq,skv,bq,bk,window,shift",
                         GEOMS + [(40, 40, 16, 16, 7, 1), (37, 53, 8, 16, 5, 3),
                                  (40, 54, 16, 16, None, 14)])
def test_live_blocks_match_mask(sq, skv, bq, bk, window, shift):
    """A block is live exactly where the causal (and window) mask keeps a
    pair, and the kernels' mask drops every pair outside it and the
    padding."""
    nq, nk = -(-sq // bq), -(-skv // bk)
    geom = dict(block_q=bq, block_k=bk, sq_valid=sq, skv_valid=skv,
                window=window, causal_shift=shift)
    for qi in range(nq):
        for ki in range(nk):
            q = (qi * bq + np.arange(bq))[:, None]
            k = (ki * bk + np.arange(bk))[None, :]
            keep = k <= q + shift
            if window is not None:
                keep &= k > q + shift - window
            assert bool(fa._block_live(qi, ki, **geom)) == keep.any()
            np.testing.assert_array_equal(np.asarray(fa._mask(qi, ki, **geom)),
                                          keep & (q < sq) & (k < skv))


@pytest.mark.parametrize("sq,skv,bq,bk,window,shift", GEOMS)
def test_clamped_index_maps(sq, skv, bq, bk, window, shift):
    """Dead causal blocks map to the nearest live block's index, so the
    grid copies each live block once and nothing for the dead ones; live
    blocks keep their own index."""
    nq, nk = -(-sq // bq), -(-skv // bk)
    geom = dict(block_q=bq, block_k=bk, sq_valid=sq, skv_valid=skv,
                window=window, causal_shift=shift)

    def live(qi, ki):
        return bool(fa._block_live(qi, ki, **geom))

    # forward and dq: K/V blocks along ki for each qi
    kv, kv_live = [], []
    for qi in range(nq):
        first, last = (int(x) for x in fa.live_k_range(
            qi, block_q=bq, block_k=bk, n_k=nk, window=window,
            causal_shift=shift))
        for ki in range(nk):
            got = int(fa._clamp(ki, first, last))
            assert got == ki if live(qi, ki) else got in (first, last)
            if ki > last:
                assert got == last
            kv.append((qi, got))
            if live(qi, ki):
                kv_live.append((qi, ki))
    assert [b for _, b in _fetches(kv)] == [b for _, b in _fetches(kv_live)]
    # dk/dv: Q/dO/lse/delta blocks along qi for each ki (one head)
    qs, q_live = [], []
    for ki in range(nk):
        first, last = (int(x) for x in fa.live_q_range(
            ki, block_q=bq, block_k=bk, n_q=nq, window=window,
            causal_shift=shift))
        for qi in range(nq):
            got = int(fa._clamp(qi, first, last))
            assert got == qi if live(qi, ki) else got in (first, last)
            if qi < first:
                assert got == first
            qs.append((ki, got))
            if live(qi, ki):
                q_live.append((ki, qi))
    assert [b for _, b in _fetches(qs)] == [b for _, b in _fetches(q_live)]


@pytest.mark.parametrize("B,H,KVH,Sq,Skv,D,window", [
    (1, 6, 1, 1536, 1536, 64, None),     # GQA 6:1, 3 x 2 blocks of 512 x 768
    (1, 7, 1, 1100, 1300, 128, 300),     # GQA 7:1, ragged, window, shift
    (1, 6, 1, 1280, 1280, 128, 500),     # window across blocks
])
def test_flash_attention_default_blocks(B, H, KVH, Sq, Skv, D, window):
    """Forward and gradients at the chosen blocks, over several of them,
    against the reference."""
    key = jax.random.PRNGKey(11)
    q = rand(key, (B, H, Sq, D), jnp.float32)
    k = rand(jax.random.fold_in(key, 1), (B, KVH, Skv, D), jnp.float32)
    v = rand(jax.random.fold_in(key, 2), (B, KVH, Skv, D), jnp.float32)
    w = rand(jax.random.fold_in(key, 3), (B, H, Sq, D), jnp.float32)
    shift = Skv - Sq
    bq, bk = fa.choose_blocks(Sq, Skv)
    assert -(-Sq // bq) > 1 and -(-Skv // bk) > 1

    def f_ker(q, k, v):
        return (flash_attention(q, k, v, window, shift, None, None, True)
                * w).sum()

    def f_ref(q, k, v):
        return (ref.flash_attention_ref(q, k, v, window=window,
                                        causal_shift=shift) * w).sum()

    o = flash_attention(q, k, v, window, shift, None, None, True)
    r = ref.flash_attention_ref(q, k, v, window=window, causal_shift=shift)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=2e-5)
    gk = jax.grad(f_ker, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gk, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
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
