"""Fused flash-attention TRAINING kernel for TPU in Pallas.

The training-side twin of paged_attention.py: the SAME blocking policy
and online-softmax block update (ops/pallas/attention_core.py owns
both) applied to the contiguous case — q-blocks of one sequence's
tokens against kv blocks of the same sequence, so the [T, T]
probability matrix never materializes in HBM. Block shapes come from
attention_core.choose_flash_blocks (VMEM-budget-capped, measured on
real TPU); every score dot is [bq, D] x [D, bk] with bq targeting the
same MXU tiles the serving kernel's q-block/head folding targets, and
tools/check_dot_shapes.py ratchets both kernels against the same M >= 8
floor.

Backward is the standard two-pass flash backward (dq pass, then dk/dv
pass) via jax.custom_vjp, recomputing probabilities from the saved lse
and accumulating in f32 scratch.

Layout contract: q, k, v are [batch, seq, heads, head_dim] (the
framework's fused-attention layout); internally folded to [B*H, T, D].
Causal masking is attention_core.causal_valid per block; blocks
strictly above the diagonal are skipped outright.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import I0, NEG_INF  # noqa: F401
from . import attention_core as core


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
                l_ref, *, scale, causal, block_q, block_k):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m0, l0, acc0 = core.softmax_carry(block_q, q_ref.shape[-1])
        m_ref[:], l_ref[:], acc_ref[:] = m0, l0, acc0

    def _body():
        q = q_ref[0].astype(jnp.float32)          # [bq, d]
        k = k_ref[0].astype(jnp.float32)          # [bk, d]
        v = v_ref[0].astype(jnp.float32)          # [bk, d]
        s = core.score_dot(q, k, scale)           # [bq, bk]
        valid = (core.causal_valid(iq, ik, block_q, block_k)
                 if causal else None)
        m_ref[:], l_ref[:], acc_ref[:] = core.softmax_update(
            m_ref[:], l_ref[:], acc_ref[:], s, v, valid=valid)

    if causal:
        # skip blocks strictly above the diagonal band
        @pl.when(ik * block_k <= (iq + 1) * block_q - 1)
        def _run():
            _body()
    else:
        _body()

    @pl.when(ik == nk - 1)
    def _finalize():
        out, lse = core.softmax_finalize(m_ref[:], l_ref[:], acc_ref[:])
        o_ref[0] = out.astype(o_ref.dtype)
        lse_ref[0, 0] = lse


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc, *, scale, causal, block_q, block_k):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = core.score_dot(q, k, scale)
        if causal:
            s = jnp.where(core.causal_valid(iq, ik, block_q, block_k),
                          s, jnp.float32(NEG_INF))
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * jnp.float32(scale)
        dq_acc[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        @pl.when(ik * block_k <= (iq + 1) * block_q - 1)
        def _run():
            _body()
    else:
        _body()

    @pl.when(ik == nk - 1)
    def _fin():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, dk_acc, dv_acc, *, scale, causal, block_q,
                block_k):
    ik = pl.program_id(1)
    iq = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = core.score_dot(q, k, scale)
        if causal:
            s = jnp.where(core.causal_valid(iq, ik, block_q, block_k),
                          s, jnp.float32(NEG_INF))
        p = jnp.exp(s - lse[:, None])                       # [bq, bk]
        dv_acc[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # [bk, d]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * jnp.float32(scale)  # [bq, bk]
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # [bk, d]

    if causal:
        @pl.when(ik * block_k <= (iq + 1) * block_q - 1)
        def _run():
            _body()
    else:
        _body()

    @pl.when(iq == nq - 1)
    def _fin():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, scale, interpret):
    out, _ = _flash_fwd_impl(q, k, v, causal, scale, interpret)
    return out


def _flash_fwd_impl(q, k, v, causal, scale, interpret):
    BH, Tq, D = q.shape
    Tk = k.shape[1]
    bq, bk = core.choose_flash_blocks(Tq, Tk, D)
    grid = (BH, Tq // bq, Tk // bk)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, I0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, I0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, I0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, I0)),
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, I0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tq, D), q.dtype),
            # lse kept [BH, 1, Tq]: trailing block dims (1, bq) satisfy the
            # TPU (8, 128) tiling rule, which a [BH, Tq] layout cannot
            jax.ShapeDtypeStruct((BH, 1, Tq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        name="flash_attention_fwd",
        interpret=interpret,
    )(q, k, v)
    return out, lse


def _flash_fwd(q, k, v, causal, scale, interpret):
    out, lse = _flash_fwd_impl(q, k, v, causal, scale, interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, interpret, res, dout):
    q, k, v, out, lse = res
    BH, Tq, D = q.shape
    Tk = k.shape[1]
    bq, bk = core.choose_flash_blocks(Tq, Tk, D)
    delta = jnp.sum(out.astype(jnp.float32) * dout.astype(jnp.float32),
                    axis=-1)[:, None, :]  # [BH, 1, Tq]

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk),
        grid=(BH, Tq // bq, Tk // bk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, I0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, I0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, I0)),
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, I0)),
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, I0, i)),
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, I0, i)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, I0)),
        out_shape=jax.ShapeDtypeStruct((BH, Tq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        name="flash_attention_dq",
        interpret=interpret,
    )(q, k, v, dout, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk),
        grid=(BH, Tk // bk, Tq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, I0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, I0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, I0)),
            pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, I0)),
            pl.BlockSpec((1, 1, bq), lambda b, j, i: (b, I0, i)),
            pl.BlockSpec((1, 1, bq), lambda b, j, i: (b, I0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, I0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, I0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tk, D), k.dtype),
            jax.ShapeDtypeStruct((BH, Tk, D), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        name="flash_attention_dkv",
        interpret=interpret,
    )(q, k, v, dout, lse, delta)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_arrays(q, k, v, causal=False, scale=None,
                           interpret=False):
    """Array-level entry: q,k,v [B, T, H, D] → out [B, T, H, D]."""
    B, Tq, H, D = q.shape
    scale = core.default_scale(scale, D)
    fold = lambda x: jnp.swapaxes(x, 1, 2).reshape(B * H, x.shape[1], D)
    out = _flash(fold(q), fold(k), fold(v), causal, scale, interpret)
    return jnp.swapaxes(out.reshape(B, H, Tq, D), 1, 2)


def _per_shard(fn, mesh, batch_axis, head_axis, q_shape):
    """`fn` per shard of `mesh`: batch over `batch_axis`, heads over
    `head_axis` (each where it divides), everything else replicated. A
    Mosaic kernel cannot be partitioned automatically — inside an
    auto-partitioned SPMD program the chip's lowering refuses the bare
    call ("wrap the call in a shard_map") — and attention is independent
    per (batch, head), so the per-shard call IS the partitioning."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    B, _, H, _ = q_shape
    part = lambda axis, n: axis if n % mesh.shape[axis] == 0 else None
    spec = P(part(batch_axis, B), None, part(head_axis, H), None)
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)


def flash_attention(q, k, v, causal=False, scale=None, interpret=None,
                    partition=None):
    """Tensor-level entry used by F.scaled_dot_product_attention.
    `partition` = (mesh, batch_axis, head_axis), handed down by the
    builder of an auto-partitioned SPMD program
    (ops.kernels_partitioned_over), runs the kernel per shard of that
    mesh; None calls it bare."""
    from ...framework.core import apply_op
    interpret = core.default_interpret(interpret)

    def fn(qa, ka, va):
        call = lambda a, b, c: flash_attention_arrays(
            a, b, c, causal=causal, scale=scale, interpret=interpret)
        if partition is not None:
            call = _per_shard(call, *partition, qa.shape)
        return call(qa, ka, va)

    return apply_op(fn, q, k, v)
