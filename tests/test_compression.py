"""Quantization numerics of the gradient-compression wire format (single
device; the collective path is covered in test_multidevice)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from hypothesis.extra import numpy as hnp

from repro.train.compression import _quantize_int8


@given(hnp.arrays(np.float32, st.integers(1, 64),
                  elements=st.floats(-100, 100, width=32)))
@settings(max_examples=100, deadline=None)
def test_int8_quantization_error_bound(x):
    g = jnp.asarray(x)
    amax = float(jnp.max(jnp.abs(g)))
    scale = max(amax / 127.0, 1e-12)
    q = _quantize_int8(g, scale)
    deq = q.astype(jnp.float32) * scale
    # absolute error bounded by half a quantization step
    assert float(jnp.max(jnp.abs(deq - g))) <= scale * 0.5 + 1e-7


def test_int8_range():
    g = jnp.asarray([-1e9, 1e9, 0.0], jnp.float32)
    q = _quantize_int8(g, 1.0)
    assert int(q.min()) >= -127 and int(q.max()) <= 127
