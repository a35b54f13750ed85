"""Fused LayerNorm Pallas kernel (forward + custom VJP).

Replaces the reference's fused layer_norm CUDA kernel
(paddle/fluid/operators/layer_norm_kernel.cu.h): one VMEM pass computes
mean/rstd and the normalized output; backward recomputes the cheap
statistics and fuses all three gradients. Rows are tiled over the grid;
the feature dimension stays resident in VMEM (hidden sizes up to ~32k fp32
fit comfortably in 16MB).
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import I0  # noqa: F401


def _fwd_kernel(x_ref, w_ref, b_ref, o_ref, mu_ref, rstd_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + jnp.float32(eps))
    y = xc * rstd
    o_ref[:] = (y * w_ref[:].astype(jnp.float32) +
                b_ref[:].astype(jnp.float32)).astype(o_ref.dtype)
    mu_ref[:] = mu
    rstd_ref[:] = rstd


def _bwd_kernel(x_ref, w_ref, mu_ref, rstd_ref, do_ref, dx_ref, dw_ref,
                db_ref):
    i = pl.program_id(0)
    x = x_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    do = do_ref[:].astype(jnp.float32)
    mu = mu_ref[:]
    rstd = rstd_ref[:]
    xhat = (x - mu) * rstd
    wdy = do * w
    c1 = jnp.mean(xhat * wdy, axis=-1, keepdims=True)
    c2 = jnp.mean(wdy, axis=-1, keepdims=True)
    dx = (wdy - xhat * c1 - c2) * rstd
    dx_ref[:] = dx.astype(dx_ref.dtype)

    @pl.when(i == 0)
    def _init():
        dw_ref[:] = jnp.zeros_like(dw_ref)
        db_ref[:] = jnp.zeros_like(db_ref)
    dw_ref[:] += jnp.sum(do * xhat, axis=0).astype(dw_ref.dtype)
    db_ref[:] += jnp.sum(do, axis=0).astype(db_ref.dtype)


def _choose_rows(n_rows):
    r = min(256, n_rows)
    while n_rows % r:
        r //= 2
    return max(r, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _layer_norm(x2d, w, b, eps, interpret):
    out, _, _ = _ln_fwd_impl(x2d, w, b, eps, interpret)
    return out


def _ln_fwd_impl(x2d, w, b, eps, interpret):
    R, C = x2d.shape
    br = _choose_rows(R)
    out, mu, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=(R // br,),
        in_specs=[
            pl.BlockSpec((br, C), lambda i: (i, I0)),
            pl.BlockSpec((C,), lambda i: (I0,)),
            pl.BlockSpec((C,), lambda i: (I0,)),
        ],
        out_specs=[
            pl.BlockSpec((br, C), lambda i: (i, I0)),
            # stats kept [R, 1]: 1D partial blocks trip XLA/Mosaic layout
            # disagreements on TPU; a trailing unit dim satisfies tiling
            pl.BlockSpec((br, 1), lambda i: (i, I0)),
            pl.BlockSpec((br, 1), lambda i: (i, I0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, C), x2d.dtype),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
        ],
        name="layer_norm_fwd",
        interpret=interpret,
    )(x2d, w, b)
    return out, mu, rstd


def _ln_fwd(x2d, w, b, eps, interpret):
    out, mu, rstd = _ln_fwd_impl(x2d, w, b, eps, interpret)
    return out, (x2d, w, mu, rstd)


def _ln_bwd(eps, interpret, res, dout):
    x2d, w, mu, rstd = res
    R, C = x2d.shape
    br = _choose_rows(R)
    dx, dw, db = pl.pallas_call(
        _bwd_kernel,
        grid=(R // br,),
        in_specs=[
            pl.BlockSpec((br, C), lambda i: (i, I0)),
            pl.BlockSpec((C,), lambda i: (I0,)),
            pl.BlockSpec((br, 1), lambda i: (i, I0)),
            pl.BlockSpec((br, 1), lambda i: (i, I0)),
            pl.BlockSpec((br, C), lambda i: (i, I0)),
        ],
        out_specs=[
            pl.BlockSpec((br, C), lambda i: (i, I0)),
            pl.BlockSpec((C,), lambda i: (I0,)),
            pl.BlockSpec((C,), lambda i: (I0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, C), x2d.dtype),
            jax.ShapeDtypeStruct((C,), jnp.float32),
            jax.ShapeDtypeStruct((C,), jnp.float32),
        ],
        name="layer_norm_bwd",
        interpret=interpret,
    )(x2d, w, mu, rstd, dout)
    return dx, dw.astype(w.dtype), db.astype(w.dtype)


_layer_norm.defvjp(_ln_fwd, _ln_bwd)


def layer_norm(x, weight, bias, eps=1e-5, interpret=None):
    """Array-level fused layer norm over the last dim."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    shape = x.shape
    x2d = x.reshape(-1, shape[-1])
    out = _layer_norm(x2d, weight.reshape(-1), bias.reshape(-1), eps,
                      interpret)
    return out.reshape(shape)
