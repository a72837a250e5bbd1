"""MoE dispatch properties: dropless at cf=E, grouping-invariance of the
dropless result, routing mass conservation, load-balance signal."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.models.module import init_params
from repro.models.moe import apply_moe, moe_specs


def make(key, d=16, f=32, E=4):
    return init_params(moe_specs(d, f, E), key)


def x_of(key, B, S, d):
    return jax.random.normal(key, (B, S, d), jnp.float32)


def test_dropless_when_capacity_factor_is_E():
    key = jax.random.PRNGKey(0)
    p = make(key)
    x = x_of(jax.random.fold_in(key, 1), 2, 32, 16)
    _, aux = apply_moe(p, x, top_k=2, act="silu", capacity_factor=4.0)
    assert float(aux["dropped_frac"]) == 0.0


def test_grouping_invariance_dropless():
    """With no drops, group count must not change the output."""
    key = jax.random.PRNGKey(1)
    p = make(key)
    x = x_of(jax.random.fold_in(key, 2), 2, 32, 16)
    outs = []
    for g in (1, 4, 16):
        y, aux = apply_moe(p, x, top_k=2, act="silu", capacity_factor=4.0,
                           n_groups=g)
        assert float(aux["dropped_frac"]) == 0.0
        outs.append(np.asarray(y))
    np.testing.assert_allclose(outs[0], outs[1], atol=2e-5)
    np.testing.assert_allclose(outs[0], outs[2], atol=2e-5)


def test_capacity_drops_increase_as_cf_shrinks():
    key = jax.random.PRNGKey(2)
    p = make(key)
    x = x_of(jax.random.fold_in(key, 3), 4, 64, 16)
    drops = []
    for cf in (4.0, 1.0, 0.5):
        _, aux = apply_moe(p, x, top_k=2, act="silu", capacity_factor=cf)
        drops.append(float(aux["dropped_frac"]))
    assert drops[0] <= drops[1] <= drops[2]
    assert drops[0] == 0.0


def test_lb_loss_detects_imbalance():
    """A router biased to one expert must score a higher balance loss."""
    key = jax.random.PRNGKey(3)
    p = make(key)
    x = x_of(jax.random.fold_in(key, 4), 2, 64, 16)
    _, aux_bal = apply_moe(p, x, top_k=2, act="silu")
    p_biased = dict(p)
    p_biased["router"] = p["router"].at[:, 0].add(100.0)
    _, aux_bias = apply_moe(p_biased, x, top_k=2, act="silu")
    assert float(aux_bias["lb_loss"]) > float(aux_bal["lb_loss"])


def test_moe_is_differentiable():
    key = jax.random.PRNGKey(4)
    p = make(key)
    x = x_of(jax.random.fold_in(key, 5), 2, 16, 16)

    def loss(p):
        y, aux = apply_moe(p, x, top_k=2, act="silu")
        return jnp.sum(jnp.square(y)) + 0.01 * aux["lb_loss"]

    g = jax.grad(loss)(p)
    gn = sum(float(jnp.sum(jnp.abs(v))) for v in jax.tree.leaves(g))
    assert np.isfinite(gn) and gn > 0
    # router receives gradient through the gate weights
    assert float(jnp.sum(jnp.abs(g["router"]))) > 0


@given(st.integers(0, 50))
@settings(max_examples=15, deadline=None)
def test_output_tokens_bounded_by_expert_outputs(seed):
    """Each output token is a convex-ish combination: finite, no NaN, and
    zero for fully-dropped tokens only."""
    key = jax.random.PRNGKey(seed)
    p = make(key)
    x = x_of(jax.random.fold_in(key, 1), 1, 16, 16)
    y, aux = apply_moe(p, x, top_k=2, act="silu", capacity_factor=0.5)
    assert not bool(jnp.isnan(y).any())
    assert np.isfinite(float(jnp.max(jnp.abs(y))))
