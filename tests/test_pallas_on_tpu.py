"""Real-TPU Mosaic lowering AND results proof for three Pallas kernels
(interpret=False): fwd/bwd of flash_attention, layer_norm and
softmax_xent against their XLA references.

The CPU suite runs the kernels with interpret=True, and
tests/test_chip_compile.py compiles the main path's kernels for a
described v5e without a chip; this file RUNS on the chip. In the normal
suite (conftest pins the CPU backend) its one test skips from inside the
test. On the chip, through the chip tool and as the only process:

    chiprun -- python tests/test_pallas_on_tpu.py

which exits non-zero where there is no TPU (a run that proved nothing
is not a pass). `python chip_smoke.py` is the wider proof: the whole
train step and serving step, with these kernels in place.
"""
import numpy as np


def _on_tpu():
    import jax
    return jax.default_backend() == "tpu"


def run_all():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_arrays
    from paddle_tpu.ops.pallas.layer_norm import layer_norm
    from paddle_tpu.ops.pallas.softmax_xent import softmax_xent_arrays

    rng = np.random.RandomState(0)
    B, T, H, D = 2, 1024, 8, 64
    q, k, v = (jnp.asarray(rng.randn(B, T, H, D), jnp.bfloat16)
               for _ in range(3))

    def fa(q, k, v):
        return flash_attention_arrays(q, k, v, causal=True, interpret=False)

    out = jax.jit(fa)(q, k, v)

    def ref_fn(q):
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) / np.sqrt(D)
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e30)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1),
                          v.astype(jnp.float32))

    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref_fn(q))))
    assert err < 2e-2, f"flash fwd {err}"
    g = jax.jit(jax.grad(
        lambda q: fa(q, k, v).astype(jnp.float32).sum()))(q)
    gref = jax.grad(lambda q: ref_fn(q).sum())(q)
    gerr = float(jnp.max(jnp.abs(
        g.astype(jnp.float32) - gref.astype(jnp.float32))))
    assert gerr < 5e-2, f"flash bwd {gerr}"

    x = jnp.asarray(rng.randn(512, 1024), jnp.float32)
    w = jnp.asarray(rng.randn(1024), jnp.float32)
    b = jnp.asarray(rng.randn(1024), jnp.float32)
    y = jax.jit(lambda x: layer_norm(x, w, b, 1e-5, interpret=False))(x)

    def ln_ref(x):
        mu = x.mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(x.var(-1, keepdims=True) + 1e-5) * w + b

    assert float(jnp.max(jnp.abs(y - ln_ref(x)))) < 1e-4
    gl = jax.jit(jax.grad(
        lambda x: layer_norm(x, w, b, 1e-5, interpret=False).sum()))(x)
    glref = jax.grad(lambda x: ln_ref(x).sum())(x)
    assert float(jnp.max(jnp.abs(gl - glref))) < 1e-3

    N, V = 2048, 50304
    logits = jnp.asarray(rng.randn(N, V), jnp.float32)
    labels = jnp.asarray(rng.randint(0, V, N), jnp.int32)
    loss = jax.jit(
        lambda l: softmax_xent_arrays(l, labels, interpret=False))(logits)
    lref = jax.nn.logsumexp(logits, -1) - logits[jnp.arange(N), labels]
    assert float(jnp.max(jnp.abs(loss - lref))) < 1e-3
    gx = jax.jit(jax.grad(
        lambda l: softmax_xent_arrays(l, labels,
                                      interpret=False).sum()))(logits)
    gxref = jax.nn.softmax(logits, -1) - jax.nn.one_hot(labels, V)
    assert float(jnp.max(jnp.abs(gx - gxref))) < 1e-3
    return True


def test_pallas_kernels_lower_on_tpu():
    import pytest
    if not _on_tpu():
        pytest.skip("requires the real TPU backend")
    assert run_all()


if __name__ == "__main__":
    import sys
    import os
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), ".."))
    if not _on_tpu():
        print("SKIP: not on TPU — nothing was proved")
        raise SystemExit(2)
    run_all()
    print("ok: all Pallas kernels lower and match on real TPU")
