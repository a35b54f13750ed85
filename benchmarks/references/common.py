"""What the plain references share: matmuls at a stated precision, the
norm, the loss. Straightforward jax.numpy; imports nothing of the
program.

`prec` names the arithmetic of a whole forward pass:
  "f32"   float32 weights and activations, every matmul at
          Precision.HIGHEST — THE reference
  "bf16"  weights and activations held in bfloat16, matmuls accumulate
          in float32, norms and softmax in float32 — the control for a
          float32 configuration
  "fp8"   as "bf16", with both operands of every matmul rounded to
          float8_e4m3fn under a per-tensor scale — the control for a
          bfloat16 configuration
"""
import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("f32", "bf16", "fp8")
# the control of a configuration that states the key's precision
CONTROL_OF = {"float32": "bf16", "bfloat16": "fp8"}


def act_dtype(prec):
    return jnp.float32 if prec == "f32" else jnp.bfloat16


def _q8(x):
    """x rounded to float8_e4m3fn under a per-tensor scale, returned in
    bfloat16. The rounding is straight-through for gradients (a cotangent
    carried in float8 would underflow), as fp8 training recipes do."""
    x32 = x.astype(jnp.float32)
    s = jax.lax.stop_gradient(
        jnp.maximum(jnp.max(jnp.abs(x32)), 1e-30) / 448.0)
    q = (x32 / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return (x32 + jax.lax.stop_gradient(q - x32)).astype(jnp.bfloat16)


def mm(x, w, prec, spec=None):
    """x @ w (or einsum `spec`) at the stated precision; the result is
    in the activations' dtype."""
    f = (lambda a, b, **kw: jnp.einsum(spec, a, b, **kw)) if spec \
        else jnp.matmul
    if prec == "f32":
        return f(x.astype(jnp.float32), w.astype(jnp.float32),
                 precision=jax.lax.Precision.HIGHEST)
    if prec == "bf16":
        return f(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                 preferred_element_type=jnp.float32).astype(jnp.bfloat16)
    if prec == "fp8":
        out = f(_q8(x), _q8(w), preferred_element_type=jnp.float32)
        return out.astype(jnp.bfloat16)
    raise ValueError(f"unknown precision {prec!r}")


def layer_norm(x, g, b, eps, prec):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32) \
        + b.astype(jnp.float32)
    return y.astype(act_dtype(prec))


def token_xent(logits, labels):
    """-log softmax(logits)[label] at every position, float32."""
    lg = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return lse - picked


def mean_xent(logits, labels):
    """Mean over all positions of -log softmax(logits)[label], float32."""
    return token_xent(logits, labels).mean()


def weighted_xent(logits, labels, weights):
    """Sum over all positions of weights x -log softmax(logits)[label],
    over the number of positions, float32: the block-diffusion loss,
    whose weights are 1/t at the masked positions and 0 elsewhere."""
    return (weights.astype(jnp.float32) * token_xent(logits, labels)).mean()


def seed_arg(seed):
    """--seed, which may pass 2**31, folded on the host into the int32
    that make_weights' key takes."""
    return int(seed) % (2 ** 31 - 1)


def weights_from_seed(spec, seed, dtype="float32"):
    """make_weights as ONE jitted call on the device: float32 leaves
    whose values are those of the dtype the configuration states (so the
    program, which holds them in that dtype, and the reference, which
    computes in float32, start from the same numbers)."""
    return jax.jit(lambda s: make_weights(spec, s, jnp.dtype(dtype)))(
        seed_arg(seed))


def make_weights(spec, seed, round_to=jnp.float32):
    """Every leaf of `spec` ({name: (shape, kind)}) from the seed, in one
    traced computation (seeded_leaf, in the order of the sorted names)."""
    key = jax.random.PRNGKey(seed)
    return {name: seeded_leaf(key, i, shape, kind, round_to)
            for i, (name, (shape, kind)) in enumerate(sorted(spec.items()))}


def seeded_leaf(key, i, shape, kind, round_to=jnp.float32, flat=False):
    """The i-th leaf of make_weights, by its kind: "normal" N(0, 0.02),
    "normal:<std>"; "ones"; "zeros"; "sign" (+1 or -1, for a norm's gain:
    see references/mamba.py); "a_log" and "dt_bias" (the S4/Mamba inits:
    decay rates log(1..N) per channel, steps log-uniform in [1e-3,
    1e-1]). Float32, holding values of `round_to`. `flat`: the same
    values in one row, drawn so (a draw of JAX's counter-based generator
    depends on an entry's place in the row, not on the shape; the
    benchmark's tests hold the two equal)."""
    if flat and kind != "a_log":
        shape = (int(np.prod(shape)),)
    if kind.startswith("normal"):       # "normal" or "normal:<std>"
        std = float(kind.partition(":")[2] or 0.02)
        v = std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                    jnp.float32)
    elif kind == "dt_bias":     # Mamba's dt init: softplus^-1 of a
        u = jax.random.uniform(  # log-uniform step in [1e-3, 1e-1]
            jax.random.fold_in(key, i), shape, jnp.float32)
        dt = jnp.exp(u * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3))
        v = dt + jnp.log(-jnp.expm1(-dt))
    elif kind == "ones":
        v = jnp.ones(shape, jnp.float32)
    elif kind == "zeros":
        v = jnp.zeros(shape, jnp.float32)
    elif kind == "sign":
        v = jnp.where(jax.random.bernoulli(jax.random.fold_in(key, i),
                                           0.5, shape), 1.0, -1.0)
    elif kind == "a_log":
        v = jnp.broadcast_to(jnp.log(jnp.arange(
            1, shape[-1] + 1, dtype=jnp.float32)), shape)
        v = v.reshape(-1) if flat else v
    else:
        raise ValueError(f"unknown init kind {kind!r}")
    return v.astype(round_to).astype(jnp.float32)
