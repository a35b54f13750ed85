"""Normalization functionals. Parity: python/paddle/nn/functional/norm.py.

layer_norm / batch_norm are bandwidth-bound on TPU; the fused Pallas
variants live in paddle_tpu.ops.pallas and are picked up automatically by
the jit path for large shapes (see ops/__init__.py).
"""
import jax
import jax.numpy as jnp

from ...framework.core import Tensor, apply_op


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    def fn(a):
        if p == 2:
            n = jnp.sqrt(jnp.sum(a * a, axis=axis, keepdims=True))
        else:
            n = jnp.sum(jnp.abs(a) ** p, axis=axis,
                        keepdims=True) ** (1.0 / p)
        return a / jnp.maximum(n, epsilon)
    return apply_op(fn, x)


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-05,
               name=None):
    if isinstance(normalized_shape, int):
        normalized_shape = [normalized_shape]
    n_axes = len(list(normalized_shape))

    # Opt-in Pallas path (PADDLE_TPU_PALLAS_LN=1): measured on the v5e
    # bench shape [8192,1024] bf16, XLA's fused composition already sits
    # at the HBM roofline (0.054 ms vs 0.145 ms for the kernel), so the
    # compiler path is the default.
    import os
    if (n_axes == 1 and weight is not None and bias is not None
            and os.environ.get("PADDLE_TPU_PALLAS_LN") == "1"):
        from ...ops import fused_layer_norm_available
        if fused_layer_norm_available():
            from ...ops.pallas.layer_norm import layer_norm as pallas_ln
            return apply_op(
                lambda a, w, b: pallas_ln(a, w, b, eps=epsilon),
                x, weight, bias)

    def fn(a, *rest):
        axes = tuple(range(a.ndim - n_axes, a.ndim))
        dtype = a.dtype
        a32 = a.astype(jnp.float32)
        mean = jnp.mean(a32, axis=axes, keepdims=True)
        var = jnp.mean(jnp.square(a32 - mean), axis=axes, keepdims=True)
        out = (a32 - mean) * jax.lax.rsqrt(var + epsilon)
        i = 0
        if weight is not None:
            out = out * rest[i].astype(jnp.float32)
            i += 1
        if bias is not None:
            out = out + rest[i].astype(jnp.float32)
        return out.astype(dtype)

    args = [t for t in (weight, bias) if t is not None]
    return apply_op(fn, x, *args)


def rms_norm(x, weight=None, epsilon=1e-05, name=None):
    """x / sqrt(mean(x^2) + epsilon) * weight over the last axis (Zhang &
    Sennrich, 2019): no centring, no bias. The statistic and the scaling
    are float32 whatever x's dtype; the result is in x's dtype."""
    def fn(a, *rest):
        a32 = a.astype(jnp.float32)
        out = a32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(a32), axis=-1, keepdims=True) + epsilon)
        if rest:
            out = out * rest[0].astype(jnp.float32)
        return out.astype(a.dtype)

    return apply_op(fn, x, *([weight] if weight is not None else []))


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-05,
               data_format="NCHW", use_global_stats=None, name=None):
    channel_last = data_format.endswith("C") and len(data_format) > 2 or \
        data_format == "NC" and False
    ch_axis = -1 if data_format in ("NHWC", "NLC", "NDHWC") else 1
    use_batch_stats = training and not use_global_stats

    def fn(a, rm, rv, *rest):
        axes = tuple(i for i in range(a.ndim) if i != (ch_axis % a.ndim))
        a32 = a.astype(jnp.float32)
        if use_batch_stats:
            mean = jnp.mean(a32, axis=axes)
            var = jnp.var(a32, axis=axes)
        else:
            mean, var = rm.astype(jnp.float32), rv.astype(jnp.float32)
        shape = [1] * a.ndim
        shape[ch_axis % a.ndim] = a.shape[ch_axis % a.ndim]
        out = (a32 - mean.reshape(shape)) * jax.lax.rsqrt(
            var.reshape(shape) + epsilon)
        i = 0
        if weight is not None:
            out = out * rest[i].astype(jnp.float32).reshape(shape)
            i += 1
        if bias is not None:
            out = out + rest[i].astype(jnp.float32).reshape(shape)
        return out.astype(a.dtype)

    args = [t for t in (weight, bias) if t is not None]
    out = apply_op(fn, x, running_mean, running_var, *args)

    if use_batch_stats and isinstance(running_mean, Tensor):
        # update running stats out-of-graph (buffers, no grad)
        from ...framework.core import no_grad
        with no_grad():
            ch = ch_axis % len(x.shape)
            axes = tuple(i for i in range(len(x.shape)) if i != ch)
            m = jnp.mean(x.value.astype(jnp.float32), axis=axes)
            n = 1
            for i in axes:
                n *= x.shape[i]
            v = jnp.var(x.value.astype(jnp.float32), axis=axes)
            unbiased = v * n / max(n - 1, 1)
            running_mean.set_value(momentum * running_mean.value +
                                   (1 - momentum) * m)
            running_var.set_value(momentum * running_var.value +
                                  (1 - momentum) * unbiased)
    return out


def instance_norm(x, running_mean=None, running_var=None, weight=None,
                  bias=None, use_input_stats=True, momentum=0.9,
                  epsilon=1e-05, data_format="NCHW", name=None):
    def fn(a, *rest):
        axes = tuple(range(2, a.ndim))
        a32 = a.astype(jnp.float32)
        mean = jnp.mean(a32, axis=axes, keepdims=True)
        var = jnp.var(a32, axis=axes, keepdims=True)
        out = (a32 - mean) * jax.lax.rsqrt(var + epsilon)
        shape = [1, a.shape[1]] + [1] * (a.ndim - 2)
        i = 0
        if weight is not None:
            out = out * rest[i].astype(jnp.float32).reshape(shape)
            i += 1
        if bias is not None:
            out = out + rest[i].astype(jnp.float32).reshape(shape)
        return out.astype(a.dtype)
    args = [t for t in (weight, bias) if t is not None]
    return apply_op(fn, x, *args)


def group_norm(x, num_groups, epsilon=1e-05, weight=None, bias=None,
               data_format="NCHW", name=None):
    channel_last = data_format.endswith("C") and len(data_format) > 2

    def fn(a, *rest):
        if channel_last:
            a_ = jnp.moveaxis(a, -1, 1)
        else:
            a_ = a
        N, C = a_.shape[:2]
        sp = a_.shape[2:]
        g = a_.reshape((N, num_groups, C // num_groups) + sp)
        a32 = g.astype(jnp.float32)
        axes = tuple(range(2, a32.ndim))
        mean = jnp.mean(a32, axis=axes, keepdims=True)
        var = jnp.var(a32, axis=axes, keepdims=True)
        out = ((a32 - mean) * jax.lax.rsqrt(var + epsilon)).reshape(a_.shape)
        shape = [1, C] + [1] * len(sp)
        i = 0
        if weight is not None:
            out = out * rest[i].astype(jnp.float32).reshape(shape)
            i += 1
        if bias is not None:
            out = out + rest[i].astype(jnp.float32).reshape(shape)
        out = out.astype(a.dtype)
        if channel_last:
            out = jnp.moveaxis(out, 1, -1)
        return out
    args = [t for t in (weight, bias) if t is not None]
    return apply_op(fn, x, *args)


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW", name=None):
    def fn(a):
        ch_axis = 1 if not data_format.endswith("C") else a.ndim - 1
        sq = jnp.square(a.astype(jnp.float32))
        half = size // 2
        pads = [(0, 0)] * a.ndim
        pads[ch_axis] = (half, size - half - 1)
        padded = jnp.pad(sq, pads)
        acc = jnp.zeros_like(sq)
        for i in range(size):
            idx = [slice(None)] * a.ndim
            idx[ch_axis] = slice(i, i + a.shape[ch_axis])
            acc = acc + padded[tuple(idx)]
        div = (k + alpha * acc / size) ** beta
        return (a.astype(jnp.float32) / div).astype(a.dtype)
    return apply_op(fn, x)
