"""Property tests: the three WKV formulations (sequential scan, chunked,
sequence-parallel chunked) agree across shapes, chunk sizes, and decay
scales — the invariant behind §Perf iterations 1-2 and the Pallas kernel."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.models.rwkv6 import _wkv_scan, wkv_chunked, wkv_seq_parallel


def mk_inputs(seed, B, S, H, hs, decay_lo, decay_hi):
    key = jax.random.PRNGKey(seed)
    r = jax.random.normal(jax.random.fold_in(key, 0), (B, S, H, hs))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, H, hs))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, H, hs))
    w_log = -jnp.exp(jax.random.uniform(jax.random.fold_in(key, 3),
                                        (B, S, H, hs),
                                        minval=decay_lo, maxval=decay_hi))
    u = jax.random.normal(jax.random.fold_in(key, 4), (H, hs))
    return r, k, v, w_log, u


@given(st.integers(0, 100), st.sampled_from([8, 16, 32]),
       st.sampled_from([(1, 64, 2, 8), (2, 96, 1, 16), (1, 128, 3, 8)]))
@settings(max_examples=20, deadline=None)
def test_chunked_equals_scan(seed, chunk, shape):
    B, S, H, hs = shape
    r, k, v, w_log, u = mk_inputs(seed, B, S, H, hs, -2.0, 2.0)
    o_ref, _ = _wkv_scan(r, k, v, w_log, u)
    o_chk, _ = wkv_chunked(r, k, v, w_log, u, chunk=chunk)
    scale = float(jnp.max(jnp.abs(o_ref))) + 1e-9
    assert float(jnp.max(jnp.abs(o_ref - o_chk))) / scale < 1e-4


@given(st.integers(0, 100), st.sampled_from([2, 4, 8]))
@settings(max_examples=15, deadline=None)
def test_seq_parallel_equals_scan(seed, n_shards):
    B, S, H, hs = 2, 128, 2, 8
    r, k, v, w_log, u = mk_inputs(seed, B, S, H, hs, -2.0, 2.0)
    o_ref, _ = _wkv_scan(r, k, v, w_log, u)
    o_sp, _ = wkv_seq_parallel(r, k, v, w_log, u, chunk=16, n_shards=n_shards)
    scale = float(jnp.max(jnp.abs(o_ref))) + 1e-9
    assert float(jnp.max(jnp.abs(o_ref - o_sp))) / scale < 1e-4


def test_strong_decay_no_nans():
    """Extreme decay (w_log ~ -e^2.3 per step) stresses the exponent
    centering: outputs must stay finite and match the scan."""
    r, k, v, w_log, u = mk_inputs(7, 1, 96, 2, 8, 1.5, 2.1)
    o_ref, _ = _wkv_scan(r, k, v, w_log, u)
    for fn in (lambda: wkv_chunked(r, k, v, w_log, u, chunk=16)[0],
               lambda: wkv_seq_parallel(r, k, v, w_log, u, chunk=16,
                                        n_shards=4)[0]):
        o = fn()
        assert not bool(jnp.isnan(o).any())
        scale = float(jnp.max(jnp.abs(o_ref))) + 1e-9
        assert float(jnp.max(jnp.abs(o_ref - o))) / scale < 5e-3


def test_final_state_composition():
    """Seq-parallel final state == chunked final state == the sequential
    scan's final state."""
    B, S, H, hs = 1, 64, 2, 8
    r, k, v, w_log, u = mk_inputs(11, B, 2 * S, H, hs, -1.0, 1.5)
    _, fin_scan = _wkv_scan(r, k, v, w_log, u)
    _, fin_chunk = wkv_chunked(r, k, v, w_log, u, chunk=16)
    _, fin_sp = wkv_seq_parallel(r, k, v, w_log, u, chunk=16, n_shards=4)
    np.testing.assert_allclose(np.asarray(fin_chunk), np.asarray(fin_sp),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(fin_chunk), np.asarray(fin_scan),
                               rtol=2e-4, atol=2e-4)


def test_bf16_streams_stay_close():
    r, k, v, w_log, u = mk_inputs(13, 2, 128, 2, 16, -2.0, 2.0)
    o_ref, _ = _wkv_scan(r, k, v, w_log, u)
    o_bf, _ = wkv_seq_parallel(r.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
                               v.astype(jnp.bfloat16), w_log, u,
                               chunk=16, n_shards=4)
    scale = float(jnp.max(jnp.abs(o_ref))) + 1e-9
    assert float(jnp.max(jnp.abs(o_ref - o_bf.astype(jnp.float32)))) / scale < 0.03
