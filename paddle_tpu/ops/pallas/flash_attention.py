"""Fused flash-attention TRAINING kernel for TPU in Pallas.

The training-side twin of paged_attention.py: the SAME blocking policy
and online-softmax block update (ops/pallas/attention_core.py owns
both) applied to the contiguous case — q-blocks of one sequence's
tokens against kv blocks of the same sequence, so the [T, T]
probability matrix never materializes in HBM.

Two-level blocking (attention_core.choose_flash_blocks, a function of
shapes alone). The GRID block, up to 1024 x 1024, is what the pipeline
holds in VMEM: one DMA, init and finalize per 1024 rows. Inside it each
kernel body takes the block in STRIPS — tq query rows (forward, dq) or
tk kv columns (dkv) — and a strip computes only the extent of the other
axis that the causal triangle leaves visible, as ONE set of dots over
that extent, with the mask on the part the diagonal crosses and no
iota, compare or select on the rest. The extents are Python ints
(attention_core.causal_kv_tiles / causal_q_tiles), so the diagonal must
stand at a static place in the block: a SQUARE block it crosses can
only stand on it (offset 0) and has that body under pl.when; blocks
wholly below the diagonal run unmasked, blocks above it run nothing,
and a crossed block that is not square (head dims over 64, causal with
Tq != Tk) goes whole under the mask. The strips are unrolled so that
the scheduler overlaps them; the same schedule as rolled loops over
pl.ds sub-tiles ran 2 to 5 times slower on the chip (PERF.md section 6,
PR 26). attention_core.visited_tile_share says what share of the square
is computed (0.625 at T = 1024 with 256-wide strips; 1.0 before PR 26).
Every dot has M >= 128 rows at such shapes; tools/check_dot_shapes.py
ratchets both attention kernels against the same M >= 8 floor. What the
kernels cost on the chip is in PERF.md (sections 5 and 6) and the
ledger, nowhere else.

The score dots (QK^T, dO.V^T) take their operands in the stored dtype,
which is exact in the f32 they accumulate in; the second dots take the
f32 probabilities (and dS) against an f32 copy of the small operand —
on the v5e a cast of the [tq, extent] tile to bf16 cost more than it
saved (PERF.md section 6, PR 26). m, l, lse, delta and all
accumulators are f32.

Backward is the standard two-pass flash backward (dq pass, then dk/dv
pass) via jax.custom_vjp, recomputing probabilities from the saved lse.
The forward rule names the two residuals the kernel makes as remat save
points, "flash_out" and "flash_lse" (jax.ad_checkpoint.checkpoint_name):
a caller whose jax.checkpoint policy saves them (models/gpt.py's
scan_remat="names", models/decoder.py) runs the forward kernel once a
layer; under any other policy, or none, the names are identity ops.

Layout contract: q, k, v are [batch, seq, heads, head_dim] (the
framework's fused-attention layout); internally folded to [B*H, T, D].
The output is un-folded INSIDE the custom_vjp, so the saved `out` is the
[B, T, H*D] value the model consumes: full lanes at any head dim (a
[B*H, T, 64] bf16 array is stored with its lanes padded to 128, twice
the bytes) and nothing to transpose when the layer is recomputed.
The causal mask is top-left aligned (row >= column).
"""
import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import I0, NEG_INF  # noqa: F401
from . import attention_core as core


# where a grid block stands against the causal diagonal
WHOLE = "whole"        # wholly below it, or no causal mask: nothing masked
DIAGONAL = "diagonal"  # a square block ON it: the strips' extents are static
CROSSED = "crossed"    # any other block it crosses: all of it under the mask


def _on_diagonal_position(causal, offset, block_q, block_k, body):
    """Run body(where) for this grid step. `offset` is the (traced)
    distance first row - first column of its block. Without a mask, or
    wholly below the diagonal: body(WHOLE). Crossed by it: a square
    block can only stand at offset 0, so what each strip sees is a
    Python int — body(DIAGONAL); blocks that are not square (causal with
    Tq != Tk, head dims over 64) take the whole block under the mask —
    body(CROSSED). Wholly above: nothing runs."""
    if not causal:
        return body(WHOLE)
    pl.when(offset >= block_k - 1)(functools.partial(body, WHOLE))
    pl.when((offset > -block_q) & (offset < block_k - 1))(functools.partial(
        body, DIAGONAL if block_q == block_k else CROSSED))


def _extent(where, start, t, u, n, of_rows):
    """(lo, hi, mlo, mhi), Python ints: along the other axis (n steps of
    u wide) the strip of t rows (`of_rows`) or t columns that starts at
    `start` within its block computes [lo, hi), never empty, of which
    [mlo, mhi) is crossed by the diagonal and takes the mask: the tail
    of a strip of rows, the head of a strip of columns."""
    if where == WHOLE:
        return 0, n * u, 0, 0
    if where == CROSSED:
        return 0, n * u, 0, n * u
    if of_rows:
        n_full, n_visit = core.causal_kv_tiles(start, t, u, n)
        ext = 0, n_visit * u, n_full * u, n_visit * u
    else:
        first, first_full = core.causal_q_tiles(start, t, u, n)
        ext = first * u, n * u, first * u, first_full * u
    # every strip of a block on the diagonal sees some of it
    assert ext[0] < ext[1], (where, start, t, u, n)
    return ext


def _mask_crossed(s, extent, valid):
    """The scores s of the extent's [lo, hi) columns with the crossed
    part [mlo, mhi) — head, tail or all of it — set to NEG_INF wherever
    valid(shape, first column) is False; the rest passes untouched."""
    lo, hi, mlo, mhi = extent
    if mlo == mhi:
        return s
    parts = []
    if mlo > lo:
        parts.append(s[:, :mlo - lo])
    crossed = s[:, mlo - lo:mhi - lo]
    parts.append(jnp.where(valid(crossed.shape, mlo), crossed,
                           jnp.float32(NEG_INF)))
    if hi > mhi:
        parts.append(s[:, mhi - lo:])
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _f32(ref):
    return ref[0].astype(jnp.float32)


def _along_lanes(col):
    """[t, 1] -> [1, t]. As the transpose of the column spread over one
    register's lanes it goes through the transpose unit for nearly
    nothing; laid out anew as a plain reshape it took a quarter of the
    forward kernel's time (PERF.md section 6, PR 26). Lengths the
    transpose's tiling does not take (no multiple of 128) reshape."""
    t = col.shape[0]
    if t % core.MXU_ROWS:
        return col.reshape(1, t)
    return jnp.transpose(jnp.broadcast_to(col, (t, core.MXU_ROWS)))[0:1]


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *carry_refs, scale,
                causal, block_q, block_k, tiles):
    """carry_refs (m, l, acc) hold the online softmax between kv grid
    steps; with ONE kv block there is nothing to hold and none are
    passed: a strip's softmax is born and finalized in place. The mask
    needs no zeroing of probabilities here: every row sees column 0, in
    the first kv block, so no row meets a later block untouched."""
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)
    tq, tk = tiles
    d = q_ref.shape[-1]
    offset = iq * block_q - ik * block_k

    fresh = functools.partial(core.softmax_carry, d=d, column=True)

    if carry_refs:
        @pl.when(ik == 0)
        def _init():
            for ref, x in zip(carry_refs, fresh(block_q)):
                ref[:] = x

    def _body(where):
        v = _f32(v_ref)                             # [bk, d], for p.v
        for i in range(block_q // tq):
            rows = slice(i * tq, (i + 1) * tq)
            ext = _extent(where, i * tq, tq, tk, block_k // tk, True)
            lo, hi = ext[:2]
            s = core.score_dot(q_ref[0, rows, :], k_ref[0, lo:hi, :],
                               scale)               # [tq, hi - lo]
            s = _mask_crossed(s, ext, lambda shape, col: core.causal_valid(
                offset + i * tq, col, shape))
            carry = tuple(ref[rows] for ref in carry_refs) or fresh(tq)
            carry = core.softmax_update(*carry, s, v[lo:hi])
            if carry_refs:
                for ref, x in zip(carry_refs, carry):
                    ref[rows] = x
            else:
                out, lse = core.softmax_finalize(*carry)
                o_ref[0, rows, :] = out.astype(o_ref.dtype)
                lse_ref[0, :, rows] = _along_lanes(lse)

    _on_diagonal_position(causal, offset, block_q, block_k, _body)

    if carry_refs:
        @pl.when(ik == nk - 1)
        def _finalize():
            out, lse = core.softmax_finalize(*(r[:] for r in carry_refs))
            o_ref[0] = out.astype(o_ref.dtype)
            lse_ref[0] = _along_lanes(lse)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               *acc_ref, scale, causal, block_q, block_k, tiles):
    """acc_ref: the f32 dq between kv grid steps; none with one kv
    block."""
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)
    tq, tk = tiles
    offset = iq * block_q - ik * block_k

    if acc_ref:
        @pl.when(ik == 0)
        def _init():
            acc_ref[0][:] = jnp.zeros_like(acc_ref[0])

    def _body(where):
        k32 = _f32(k_ref)                           # [bk, d], for ds.k
        for i in range(block_q // tq):
            rows = slice(i * tq, (i + 1) * tq)
            ext = _extent(where, i * tq, tq, tk, block_k // tk, True)
            lo, hi = ext[:2]
            s = core.score_dot(q_ref[0, rows, :], k_ref[0, lo:hi, :],
                               scale)               # [tq, hi - lo]
            s = _mask_crossed(s, ext, lambda shape, col: core.causal_valid(
                offset + i * tq, col, shape))
            p = jnp.exp(s - lse_ref[0, 0, rows][:, None])
            dp = jax.lax.dot_general(
                do_ref[0, rows, :], v_ref[0, lo:hi, :],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - delta_ref[0, 0, rows][:, None])
            dq = jax.lax.dot_general(
                ds, k32[lo:hi], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * jnp.float32(scale)
            if acc_ref:
                acc_ref[0][rows] += dq
            else:
                dq_ref[0, rows, :] = dq.astype(dq_ref.dtype)

    _on_diagonal_position(causal, offset, block_q, block_k, _body)

    if acc_ref:
        @pl.when(ik == nk - 1)
        def _fin():
            dq_ref[0] = acc_ref[0][:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, *acc_refs, scale, causal, block_q, block_k,
                tiles):
    """Strips of kv columns, computed TRANSPOSED, [tk, rows]: lse and
    delta then broadcast along the lanes they are stored in, and all
    four dots are A.B or A.B^T — none contracts over its left operand's
    rows. acc_refs (dk, dv): the f32 sums between q grid steps; none
    where one q block sees every kv column. (Where it does not — causal
    with Tk > Tq — kv blocks wholly above the diagonal run nothing, and
    the sums, zeroed at the first step, are what writes their zeros.)"""
    ik = pl.program_id(1)
    iq = pl.program_id(2)
    nq = pl.num_programs(2)
    tq, tk = tiles
    offset = iq * block_q - ik * block_k

    if acc_refs:
        @pl.when(iq == 0)
        def _init():
            for ref in acc_refs:
                ref[:] = jnp.zeros_like(ref)

    def _body(where):
        q32 = _f32(q_ref)                           # [bq, d], for ds^T.q
        do32 = _f32(do_ref)                         # for p^T.do
        for j in range(block_k // tk):
            cols = slice(j * tk, (j + 1) * tk)
            ext = _extent(where, j * tk, tk, tq, block_q // tq, False)
            lo, hi = ext[:2]
            st = core.score_dot(k_ref[0, cols, :], q_ref[0, lo:hi, :],
                                scale)              # [tk, hi - lo]
            st = _mask_crossed(st, ext, lambda shape, row: core.causal_valid(
                offset + row, j * tk, shape, row_axis=1))
            pt = jnp.exp(st - lse_ref[0, :, lo:hi])
            dpt = jax.lax.dot_general(
                v_ref[0, cols, :], do_ref[0, lo:hi, :],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            dst = pt * (dpt - delta_ref[0, :, lo:hi])
            dk, dv = (jax.lax.dot_general(
                a, b[lo:hi], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)  # [tk, d]
                for a, b in ((dst, q32), (pt, do32)))
            dk = dk * jnp.float32(scale)
            if acc_refs:
                acc_refs[0][cols] += dk
                acc_refs[1][cols] += dv
            else:
                dk_ref[0, cols, :] = dk.astype(dk_ref.dtype)
                dv_ref[0, cols, :] = dv.astype(dv_ref.dtype)

    _on_diagonal_position(causal, offset, block_q, block_k, _body)

    if acc_refs:
        @pl.when(iq == nq - 1)
        def _fin():
            dk_ref[0] = acc_refs[0][:].astype(dk_ref.dtype)
            dv_ref[0] = acc_refs[1][:].astype(dv_ref.dtype)


def _fold(x, heads):
    """[B, T, H*D] (or [B, T, H, D]) -> the kernels' [B*H, T, D]."""
    B, T = x.shape[:2]
    x = x.reshape(B, T, heads, -1)
    return jnp.swapaxes(x, 1, 2).reshape(B * heads, T, x.shape[-1])


def _unfold(x, heads):
    """The kernels' [B*H, T, D] -> [B, T, H*D]."""
    BH, T, D = x.shape
    x = x.reshape(BH // heads, heads, T, D)
    return jnp.swapaxes(x, 1, 2).reshape(BH // heads, T, heads * D)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, heads, causal, scale, interpret):
    """q, k, v folded [B*H, T, D] -> out [B, Tq, H*D]: the un-fold is
    inside the rule, so that the residual a remat policy saves is the
    value the model consumes, with full lanes at any head dim."""
    out, _ = _flash_fwd_impl(q, k, v, causal, scale, interpret)
    return _unfold(out, heads)


def _flash_fwd_impl(q, k, v, causal, scale, interpret):
    BH, Tq, D = q.shape
    Tk = k.shape[1]
    blocks = core.choose_flash_blocks(Tq, Tk, D)
    bq, bk = blocks.block_q, blocks.block_k
    grid = (BH, Tq // bq, Tk // bk)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, tiles=blocks.fwd),
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
        # the online softmax between kv grid steps; one step holds none
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, D), jnp.float32)] * (Tk > bk),
        name="flash_attention_fwd",
        interpret=interpret,
    )(q, k, v)
    return out, lse


def _flash_fwd(q, k, v, heads, causal, scale, interpret):
    out, lse = _flash_fwd_impl(q, k, v, causal, scale, interpret)
    # named save points: a caller's remat policy that saves "flash_out"
    # and "flash_lse" keeps this kernel out of its backward pass. The
    # PRIMAL comes from the named value too: tagged only as a residual,
    # the layer's own recomputation asks for `out` again (the next
    # matmul's weight gradient needs its input) and runs the kernel twice
    out = checkpoint_name(_unfold(out, heads), "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (q, k, v, out, lse)


def _flash_bwd(heads, causal, scale, interpret, res, dout):
    q, k, v, out, lse = res
    out, dout = _fold(out, heads), _fold(dout, heads)
    BH, Tq, D = q.shape
    Tk = k.shape[1]
    blocks = core.choose_flash_blocks(Tq, Tk, D)
    bq, bk = blocks.block_q, blocks.block_k
    delta = jnp.sum(out.astype(jnp.float32) * dout.astype(jnp.float32),
                    axis=-1)[:, None, :]  # [BH, 1, Tq]

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, tiles=blocks.dq),
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
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)] * (Tk > bk),
        name="flash_attention_dq",
        interpret=interpret,
    )(q, k, v, dout, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, tiles=blocks.dkv),
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
                        pltpu.VMEM((bk, D), jnp.float32)] * (
                            Tq > bq or (causal and Tk > Tq)),
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
    out = _flash(_fold(q, H), _fold(k, H), _fold(v, H), H, causal, scale,
                 interpret)
    return out.reshape(B, Tq, H, D)


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
