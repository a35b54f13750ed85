"""The expert layer's grouped matmul (incubate/moe.py: dispatch_plan's
sorted buffer through jax.lax.ragged_dot) against an einsum: forward and
both backward products, uneven and empty groups, garbage in the rows past
the groups."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.incubate import moe as M

SIZES = [[5, 0, 17, 8], [0, 0, 0, 0], [30, 0, 0, 0], [1, 1, 1, 27],
         [8, 8, 8, 6]]


def dense(lhs, rhs, sizes):
    starts = jnp.cumsum(sizes) - sizes
    r = jnp.arange(lhs.shape[0])[:, None]
    member = (r >= starts[None]) & (r < (starts + sizes)[None])
    return jnp.einsum("mg,mk,gkn->mn", member.astype(lhs.dtype), lhs, rhs,
                      precision="highest")


@pytest.mark.parametrize("sizes", SIZES)
def test_forward_and_both_gradients(sizes):
    sizes = jnp.asarray(sizes, jnp.int32)
    n_groups, rows, K, N = 4, 40, 32, 48
    key = jax.random.PRNGKey(0)
    lhs = jax.random.normal(key, (rows, K), jnp.float32)  # unused rows too
    rhs = jax.random.normal(jax.random.fold_in(key, 1), (n_groups, K, N),
                            jnp.float32)
    mm = lambda l, r: jax.lax.ragged_dot(l, r, sizes, precision="highest")
    out = mm(lhs, rhs)
    np.testing.assert_allclose(out, dense(lhs, rhs, sizes), atol=1e-4)
    # rows that hold no group member are zero, not garbage
    assert not np.asarray(out)[int(sizes.sum()):].any()
    cot = jax.random.normal(jax.random.fold_in(key, 2), (rows, N))
    got = jax.grad(lambda l, r: (mm(l, r) * cot).sum(), (0, 1))(lhs, rhs)
    ref = jax.grad(lambda l, r: (dense(l, r, sizes) * cot).sum(),
                   (0, 1))(lhs, rhs)
    for a, b in zip(got, ref):
        np.testing.assert_allclose(a, b, atol=2e-4)
    assert not np.asarray(got[0])[int(sizes.sum()):].any()
    assert not np.asarray(got[1])[np.asarray(sizes) == 0].any()


def test_bf16_operands_accumulate_in_float32():
    sizes = jnp.asarray([20, 3, 0, 40], jnp.int32)
    key = jax.random.PRNGKey(1)
    lhs = jax.random.normal(key, (70, 128)).astype(jnp.bfloat16)
    rhs = (0.1 * jax.random.normal(jax.random.fold_in(key, 1),
                                   (4, 128, 256))).astype(jnp.bfloat16)
    out = jax.lax.ragged_dot(lhs, rhs, sizes)
    assert out.dtype == jnp.bfloat16
    want = dense(lhs.astype(jnp.float32), rhs.astype(jnp.float32), sizes)
    np.testing.assert_allclose(out.astype(jnp.float32), want, atol=0.05,
                               rtol=0.02)


@pytest.mark.parametrize("held", [[2, 3, 4], [0], [5, 6, 7]],
                         ids=["middle", "one", "last"])
def test_dispatch_plan_sorts_the_held_assignments_by_expert(held):
    """Groups contiguous from row 0 in expert order, assignment order
    kept inside a group (the sort is stable); pos and src are each
    other's inverse on the held assignments and point nowhere off them."""
    N, k, E = 50, 3, 8
    rng = np.random.RandomState(len(held))
    chosen = np.stack([rng.permutation(E)[:k] for _ in range(N)])
    first, n_local = held[0], len(held)
    sizes, pos, src = M.dispatch_plan(jnp.asarray(chosen, jnp.int32), first,
                                      n_local)
    sizes, pos, src = map(np.asarray, (sizes, pos, src))
    n_rows = N * min(k, n_local)
    assert src.shape == (n_rows,) and pos.shape == (N, k)
    flat = chosen.reshape(-1)
    assert list(sizes) == [int((flat == e).sum()) for e in held]
    total = int(sizes.sum())
    assert (src[total:] == N * k).all() and (src[:total] < N * k).all()
    want = np.concatenate([np.flatnonzero(flat == e) for e in held])
    np.testing.assert_array_equal(src[:total], want)
    is_held = np.isin(flat, held)
    assert (pos.reshape(-1)[~is_held] == n_rows).all()
    np.testing.assert_array_equal(pos.reshape(-1)[src[:total]],
                                  np.arange(total))
