"""Stochastic rounding + low-precision optimizer state.

Closes the 1.3B single-chip precision caveat (VERDICT r3 #4): without
f32 master weights, per-step updates below a bf16 parameter's ulp round
away and training silently stalls.
With `_stochastic_rounding`, the f32->bf16 downcast adds uniform sub-ulp
noise before truncation, so those updates accumulate IN EXPECTATION —
master-weight-grade convergence at zero extra HBM. `_state_dtype=bf16`
additionally halves accumulator memory (velocity/moments), relying on the
same rounding for the (1-beta) tails.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.optimizer import SGD, Momentum


def _drift(sr, steps=1000, n=4096):
    o = SGD(learning_rate=1.0, parameters=[])
    o._stochastic_rounding = sr
    p = {"w": jnp.full((n,), 1.0, jnp.bfloat16)}
    s = {"w": o.init_leaf_state(p["w"])}
    g = {"w": jnp.full((n,), 1e-5, jnp.float32)}  # 1e-5 << ulp(1.0)=2^-7
    for i in range(1, steps + 1):
        p, s = o.apply_gradients_tree(p, g, s, 1.0, float(i))
    return float(jnp.mean(p["w"].astype(jnp.float32)))


def test_plain_rounding_freezes_sub_ulp_updates():
    """The failure mode SR exists for: bf16 params ignore tiny updates."""
    assert _drift(sr=False) == 1.0


def test_stochastic_rounding_accumulates_in_expectation():
    # 1000 steps x 1e-5 -> expected 0.99; SR mean error ~ ulp/sqrt(n*steps)
    d = _drift(sr=True)
    assert abs(d - 0.99) < 2e-3, d


def test_sr_is_unbiased_not_just_noisy():
    """Zero gradient must leave params EXACTLY unchanged (the +noise
    truncation of an exact bf16 value is the identity)."""
    o = SGD(learning_rate=1.0, parameters=[])
    o._stochastic_rounding = True
    p = {"w": jnp.asarray(np.linspace(-2, 2, 256), jnp.bfloat16)}
    s = {"w": o.init_leaf_state(p["w"])}
    g = {"w": jnp.zeros((256,), jnp.float32)}
    p2, _ = o.apply_gradients_tree(p, g, s, 1.0, 1.0)
    np.testing.assert_array_equal(np.asarray(p["w"], np.float32),
                                  np.asarray(p2["w"], np.float32))


def test_state_dtype_bf16_halves_state():
    o = Momentum(learning_rate=0.1, momentum=0.9, parameters=[])
    o._state_dtype = jnp.bfloat16
    st = o.init_leaf_state(jnp.zeros((8,), jnp.bfloat16))
    assert st[0].dtype == jnp.bfloat16
    o2 = Momentum(learning_rate=0.1, momentum=0.9, parameters=[])
    assert o2.init_leaf_state(jnp.zeros((8,), jnp.bfloat16))[0].dtype \
        == jnp.float32  # default unchanged


def test_momentum_bf16_state_sr_trains():
    """End-to-end: bf16 params + bf16 velocity + SR reach the same loss
    neighborhood as the f32-state run on a small regression task."""
    def train(state_dtype, sr):
        rs = np.random.RandomState(0)
        X = jnp.asarray(rs.randn(64, 16), jnp.float32)
        w_true = jnp.asarray(rs.randn(16, 1), jnp.float32)
        Y = X @ w_true
        o = Momentum(learning_rate=0.02, momentum=0.9, parameters=[])
        o._state_dtype = state_dtype
        o._stochastic_rounding = sr
        p = {"w": jnp.zeros((16, 1), jnp.bfloat16)}
        s = {"w": o.init_leaf_state(p["w"])}
        import jax
        for i in range(1, 201):
            def loss_fn(pp):
                return jnp.mean((X @ pp["w"].astype(jnp.float32) - Y) ** 2)
            g = jax.grad(loss_fn)(p)
            g = {"w": g["w"].astype(jnp.float32)}
            p, s = o.apply_gradients_tree(p, g, s, 0.02, float(i))
        return float(jnp.mean((X @ p["w"].astype(jnp.float32) - Y) ** 2))

    ref = train(None, False)
    low = train(jnp.bfloat16, True)
    assert low < max(2.5 * ref, 0.05), (ref, low)
