"""Global RNG state.

Parity: paddle/fluid/framework/generator.cc (paddle.seed / rng state).
TPU-native design: JAX PRNG is functional (threaded keys), so we keep one
global key that is split per draw in eager mode. Inside a traced/jitted
region (jit.to_static, trainer steps), drawing from a Python global would
bake the randomness into the compilation; `rng_scope` therefore lets the
functional path thread an explicit key — each draw folds in a counter, so
a given trace is deterministic in the key argument (vary the key per step).
"""
import threading

import jax
import jax.numpy as jnp

__all__ = ["seed", "get_rng_state", "set_rng_state", "split_key", "rng_scope"]


class _RNGState(threading.local):
    # `key` is created lazily: building it here would run an eager op at
    # import time, initializing the JAX backend — and `import
    # paddle_tpu` must initialise none: the process that imports the
    # package may be a parent that has to stay off the chip.
    def __init__(self):
        self.key = None
        self.scope_key = None
        self.scope_counter = 0


_state = _RNGState()


def _key():
    if _state.key is None:
        _state.key = jax.random.key(0)
    return _state.key


def seed(s):
    _state.key = jax.random.key(int(s))
    return _state.key


def get_rng_state():
    return _key()


def set_rng_state(key):
    _state.key = key


class rng_scope:
    """Bind an explicit key for draws inside a traced function."""

    def __init__(self, key):
        self.key = key

    def __enter__(self):
        self.prev = (_state.scope_key, _state.scope_counter)
        _state.scope_key = self.key
        _state.scope_counter = 0
        return self

    def __exit__(self, *exc):
        _state.scope_key, _state.scope_counter = self.prev
        return False


def split_key():
    """Return a fresh PRNG key for one random draw."""
    if _state.scope_key is not None:
        _state.scope_counter += 1
        return jax.random.fold_in(_state.scope_key, _state.scope_counter)
    _state.key, sub = jax.random.split(_key())
    return sub
