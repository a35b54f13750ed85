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
wholly below the diagonal run unmasked, blocks above it run nothing
(and move nothing: the index maps hold such a step at the last block
that was computed, and the pipeline copies no block twice), and a
crossed block that is not square (head dims over 64, causal with
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
framework's fused-attention layout), and the kernels take them AS THEY
LIE, as [B, T, H*D] (a free reshape), and write out, dq, dk, dv the same
way: a head is picked by the BlockSpec index map, not by a transpose.
The grid is (batch, head group, q block, kv block) and a block is
(1, block_q, g*D) at (b, i, group): g = attention_core.heads_per_block,
a function of the shape alone. One head where D is a multiple of 128;
two side by side where D = 64, the lanes of one head zeroed in the strip
that enters a score dot (the zeros add nothing to a 128-deep contraction,
which takes the MXU the passes a 64-deep one takes) and each head's half
of a [rows, 128] product picked by a lane select: the matmul count of
two heads, half the bytes of a [B*H, T, 64] array (stored with its lanes
padded to 128), and none of the 16 transposing copies a layer that the
fold cost GPT-medium's step. lse and delta stay [B*H, 1, T], a block of
g rows; delta = rowsum(out * dout) is made inside the dq kernel, from
the out and dout blocks as they lie, and handed to dkv. Shapes no lane block
fits (head dims 80 or 96, an odd head count at 64) have their heads
folded into the batch, [B*H, T, D]: the same kernels with one head, a
transposing copy of every array each way. Either way the saved `out` is
the [B, T, H*D] value the model consumes. profiler.monitor counts which
a traced call got (`flash.calls.direct`, `.direct.g<g>`,
`flash.calls.folded`) and the call's ops stand under a scope of that
name.
The causal mask is top-left aligned (row >= column).

Grouped key/value heads: k, v may come [batch, seq, kv_heads, head_dim]
with kv_heads a divisor of heads; query head i reads key/value head
i // group, group = heads / kv_heads. Nothing is repeated in HBM: forward
and dq walk the query heads and their kv-like index maps pick block
`head // group`; dkv walks the KEY/VALUE heads, the group's members on
one more grid axis inside the kv block's, dk and dv summed over members
and q blocks in the kernel's f32 scratch and written [B, Tk, kv_heads*D].
This takes one head to a lane block (D a multiple of 128); at other head
dims the entry repeats k, v to the query heads' count and the equal-heads
kernels run. Window (`window`, with causal, Tq == Tk): a query sees a
key only if query - key < window. The band has two edges, and a block is
skipped (and, by the index maps' first_kv_block / last_q_block, not
copied) where it lies wholly behind the trailing one as above the
diagonal; between the edges it runs unmasked; where an edge crosses it,
the block's distance first row - first column is one of a few Python
ints (attention_core.band_offsets: -512, 0, 3584, 4096 at T = 16,384,
head dim 128, window 4,096), each with a body of its own whose strips
have static extents — a strip behind the band runs nothing, one the
trailing edge crosses masks only the tiles it crosses — whatever the
block's shape. Equal heads and no window trace to the kernels as they
were: the bodies above and a 4-D dkv grid.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import I0, NEG_INF  # noqa: F401
from . import attention_core as core


# where a grid block stands against the causal diagonal
WHOLE = "whole"        # wholly below it, or no causal mask: nothing masked
DIAGONAL = "diagonal"  # a square block ON it: the strips' extents are static
CROSSED = "crossed"    # any other block it crosses: all of it under the mask
# inside a band (causal with a window) a crossed block's position is the
# Python int first row - first column, one body per distance
# (attention_core.band_offsets), and every strip's extent is static

# the crossed parts of a strip's extent: before the clear part, after it,
# or all of it
HEAD, TAIL, ALL = "head", "tail", "all"


def _block_offset(iq, ik, block_q, block_k, lone):
    """First row - first column of this grid step's block: traced, or
    the Python int 0 where the block is the `lone` one of its grid."""
    return 0 if lone else iq * block_q - ik * block_k


def _distance(where, offset):
    """First row - first column of the block a body was built for: the
    Python int it was built at inside a band, else this grid step's."""
    return where if isinstance(where, int) else offset


def _on_diagonal_position(causal, offset, block_q, block_k, body,
                          band=None):
    """Run body(where) for this grid step. `offset` is the distance
    first row - first column of its block. Without a mask, or wholly
    below the diagonal: body(WHOLE). Crossed by it: a square block can
    only stand at offset 0, so what each strip sees is a Python int —
    body(DIAGONAL); blocks that are not square (causal with Tq != Tk,
    head dims over 64) take the whole block under the mask —
    body(CROSSED). Wholly above: nothing runs. A lone block (offset the
    int 0: sequences up to 1024) is always the crossed one, and no
    other body is built for it: half the kernel to trace and lower.
    Inside a `band` (window, the distances at which its edges cross a
    block, whether any block lies wholly inside): body(distance) under
    its own pl.when for each, body(WHOLE) for the blocks between the
    edges, nothing for those wholly behind the band or above the
    diagonal."""
    crossed = DIAGONAL if block_q == block_k else CROSSED
    if not causal:
        return body(WHOLE)
    if band is not None:
        window, distances, inside = band
        if isinstance(offset, int):
            return body(offset if offset in distances else WHOLE)
        for o in distances:
            pl.when(offset == o)(functools.partial(body, o))
        if inside:
            pl.when((offset >= block_k - 1)
                    & (offset + (block_q - 1) < window))(
                        functools.partial(body, WHOLE))
        return None
    if isinstance(offset, int):
        return body(crossed)
    pl.when(offset >= block_k - 1)(functools.partial(body, WHOLE))
    pl.when((offset > -block_q) & (offset < block_k - 1))(
        functools.partial(body, crossed))


def _extent(where, start, t, u, n, of_rows, window=None):
    """(lo, hi, clear_lo, clear_hi), Python ints: along the other axis
    (n steps of u wide) the strip of t rows (`of_rows`) or t columns that
    starts at `start` within its block computes [lo, hi), never empty,
    of which [clear_lo, clear_hi) takes no mask. What lies before and
    after the clear part is crossed by an edge and takes the mask: the
    tail of a strip of rows and the head of a strip of columns by the
    diagonal, the other end — inside a band of `window` only, where
    `where` is the block's distance first row - first column — by the
    band's trailing edge. Nothing clear: clear_lo = clear_hi = lo. None:
    the band leaves the strip nothing of this block."""
    if where == WHOLE:
        return 0, n * u, 0, n * u
    if where == CROSSED:
        return 0, n * u, 0, 0
    o = 0 if where == DIAGONAL else where
    if of_rows:
        n_full, n_visit = core.causal_kv_tiles(o + start, t, u, n)
        first, first_full = core.window_kv_tiles(
            o + start, t, u, n, window) if window else (0, 0)
    else:
        first, first_full = core.causal_q_tiles(start - o, t, u, n)
        n_full, n_visit = core.window_q_tiles(
            start - o, t, u, n, window) if window else (n, n)
    lo, hi = first * u, n_visit * u
    if lo >= hi:
        # every strip of a block on the diagonal sees some of it
        assert window, (where, start, t, u, n)
        return None
    clear_lo, clear_hi = max(first_full * u, lo), min(n_full * u, hi)
    if clear_lo >= clear_hi:
        clear_lo = clear_hi = lo
    return lo, hi, clear_lo, clear_hi


def _mask_crossed(s, extent, valid):
    """The scores s of the extent's [lo, hi) columns with the crossed
    parts — before the clear part [clear_lo, clear_hi), after it, or all
    of it — set to NEG_INF wherever valid(shape, first column, part) is
    False; the clear part passes untouched."""
    lo, hi, clear_lo, clear_hi = extent
    if (clear_lo, clear_hi) == (lo, hi):
        return s

    def masked(a, b, part):
        crossed = s[:, a - lo:b - lo]
        return jnp.where(valid(crossed.shape, a, part), crossed,
                         jnp.float32(NEG_INF))

    if clear_lo == clear_hi:
        return masked(lo, hi, ALL)
    parts = []
    if clear_lo > lo:
        parts.append(masked(lo, clear_lo, HEAD))
    parts.append(s[:, clear_lo - lo:clear_hi - lo])
    if hi > clear_hi:
        parts.append(masked(clear_hi, hi, TAIL))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _edges_valid(row0, col0, shape, part, window, of_rows):
    """The mask of a crossed part of a strip's scores, whose first row
    and column stand at row0, col0 (rows along axis 0 for a strip of
    rows, along axis 1 for dkv's transposed strips of columns): the
    diagonal's where it crosses that part (the tail of a strip of rows,
    the head of a strip of columns), the trailing edge's at the other
    end, both where nothing between them is clear."""
    axis = 0 if of_rows else 1
    ok = None
    if part == ALL or (part == TAIL) == of_rows:
        ok = core.causal_valid(row0, col0, shape, row_axis=axis)
    if window and (part == ALL or (part == HEAD) == of_rows):
        behind = core.window_valid(row0, col0, shape, window, row_axis=axis)
        ok = behind if ok is None else ok & behind
    return ok


def _f32(ref):
    return ref[0].astype(jnp.float32)


def _head_lanes(x, h, d):
    """x [rows, g*d] with the lanes of every head but h zeroed; g = 1:
    x itself. A dot that contracts the lanes of it contracts head h's d
    alone: the zeros add nothing, and a 128-deep contraction takes the
    MXU the passes a 64-deep one takes."""
    if x.shape[-1] == d:
        return x
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where((lane >= h * d) & (lane < (h + 1) * d), x,
                     jnp.zeros_like(x))


def _join_heads(parts, d):
    """parts[h] [rows, g*d], each right in head h's lanes alone (a dot
    against a whole [extent, g*d] block yields the other heads' lanes
    beside them, in the same passes) -> the [rows, g*d] tile that takes
    every head's lanes from its own part."""
    out = parts[-1]
    if len(parts) > 1:
        lane = jax.lax.broadcasted_iota(jnp.int32, out.shape, 1)
        for h in range(len(parts) - 2, -1, -1):
            out = jnp.where(lane < (h + 1) * d, parts[h], out)
    return out


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
                causal, block_q, block_k, tiles, heads, lone, band=None):
    """One grid step: a [block_q, heads * d] block of q — `heads` heads
    side by side in its lanes, as the model's [B, T, H*D] holds them —
    against a kv block of the same heads. carry_refs (m, l, acc), each
    with a leading axis of `heads`, hold the online softmax between kv
    grid steps; with ONE kv block there is nothing to hold and none are
    passed: a strip's softmax is born and finalized in place. The mask
    needs no zeroing of probabilities here: every row sees column 0, in
    the first kv block, so no row meets a later block untouched. (Inside
    a band a row's first block may hold nothing it sees: the carry then
    takes a finite NEG_INF maximum, which the row's first visible column,
    always met later, wipes with alpha = 0.)"""
    window = band and band[0]
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)
    tq, tk = tiles
    w = q_ref.shape[-1]
    d = w // heads
    offset = _block_offset(iq, ik, block_q, block_k, lone)

    fresh = functools.partial(core.softmax_carry, d=w, column=True)

    if carry_refs:
        @pl.when(ik == 0)
        def _init():
            for ref, x in zip(carry_refs, fresh(block_q)):
                for h in range(heads):
                    ref[h] = x

    def _body(where):
        v = _f32(v_ref)                             # [bk, w], for p.v
        for i in range(block_q // tq):
            rows = slice(i * tq, (i + 1) * tq)
            ext = _extent(where, i * tq, tq, tk, block_k // tk, True,
                          window)
            if ext is None:
                assert carry_refs
                continue
            lo, hi = ext[:2]
            # every head of the block sees the same mask: made once
            valid = functools.cache(lambda shape, col, part: _edges_valid(
                _distance(where, offset) + i * tq, col, shape, part, window,
                True))
            outs = []
            for h in range(heads):
                s = core.score_dot(_head_lanes(q_ref[0, rows, :], h, d),
                                   k_ref[0, lo:hi, :], scale)
                s = _mask_crossed(s, ext, valid)    # [tq, hi - lo]
                carry = tuple(ref[h, rows] for ref in carry_refs) \
                    or fresh(tq)
                carry = core.softmax_update(*carry, s, v[lo:hi])
                if carry_refs:
                    for ref, x in zip(carry_refs, carry):
                        ref[h, rows] = x
                else:
                    out, lse = core.softmax_finalize(*carry)
                    outs.append(out)
                    lse_ref[h, :, rows] = _along_lanes(lse)
            if outs:
                o_ref[0, rows, :] = _join_heads(outs, d).astype(o_ref.dtype)

    _on_diagonal_position(causal, offset, block_q, block_k, _body, band)

    if carry_refs:
        @pl.when(ik == nk - 1)
        def _finalize():
            outs = []
            for h in range(heads):
                out, lse = core.softmax_finalize(*(r[h] for r in carry_refs))
                outs.append(out)
                lse_ref[h] = _along_lanes(lse)
            o_ref[0] = _join_heads(outs, d).astype(o_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref,
               delta_ref, *acc_ref, scale, causal, block_q, block_k, tiles,
               heads, lone, band=None):
    """delta = rowsum(out * dout) a head is made HERE, from the out and
    dout blocks as they lie, and is an output too, which dkv reads. With
    ONE kv block each strip makes its own as it goes (a column, as the
    strip uses it, and work the scheduler puts beside the other strips'
    dots); with several, a q block's first kv step makes the block's
    before anything else and the strips read it back. acc_ref: the f32
    dq between kv grid steps; none with one kv block."""
    window = band and band[0]
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)
    tq, tk = tiles
    d = q_ref.shape[-1] // heads
    offset = _block_offset(iq, ik, block_q, block_k, lone)
    strips = [slice(i * tq, (i + 1) * tq) for i in range(block_q // tq)]

    def delta_of(rows):
        """[tq, 1] a head, and written along delta_ref's lanes."""
        prod = (o_ref[0, rows, :].astype(jnp.float32)
                * do_ref[0, rows, :].astype(jnp.float32))
        cols = [jnp.sum(_head_lanes(prod, h, d), axis=1, keepdims=True)
                for h in range(heads)]
        for h, col in enumerate(cols):
            delta_ref[h, :, rows] = _along_lanes(col)
        return cols

    if acc_ref:
        @pl.when(ik == 0)
        def _init():
            for rows in strips:
                delta_of(rows)
            acc_ref[0][:] = jnp.zeros_like(acc_ref[0])

    def _body(where):
        k32 = _f32(k_ref)                           # [bk, w], for ds.k
        for i, rows in enumerate(strips):
            ext = _extent(where, i * tq, tq, tk, block_k // tk, True,
                          window)
            if ext is None:
                assert acc_ref
                continue
            lo, hi = ext[:2]
            valid = functools.cache(lambda shape, col, part: _edges_valid(
                _distance(where, offset) + i * tq, col, shape, part, window,
                True))
            delta = [delta_ref[h, 0, rows][:, None] for h in range(heads)] \
                if acc_ref else delta_of(rows)
            dqs = []
            for h in range(heads):
                s = core.score_dot(_head_lanes(q_ref[0, rows, :], h, d),
                                   k_ref[0, lo:hi, :], scale)
                s = _mask_crossed(s, ext, valid)    # [tq, hi - lo]
                p = jnp.exp(s - lse_ref[h, 0, rows][:, None])
                dp = jax.lax.dot_general(
                    _head_lanes(do_ref[0, rows, :], h, d),
                    v_ref[0, lo:hi, :], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                ds = p * (dp - delta[h])
                dqs.append(jax.lax.dot_general(
                    ds, k32[lo:hi], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
            dq = _join_heads(dqs, d) * jnp.float32(scale)
            if acc_ref:
                acc_ref[0][rows] += dq
            else:
                dq_ref[0, rows, :] = dq.astype(dq_ref.dtype)

    _on_diagonal_position(causal, offset, block_q, block_k, _body, band)

    if acc_ref:
        @pl.when(ik == nk - 1)
        def _fin():
            dq_ref[0] = acc_ref[0][:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, *acc_refs, scale, causal, block_q, block_k,
                tiles, heads, lone, band=None, group=1):
    """Strips of kv columns, computed TRANSPOSED, [tk, rows]: lse and
    delta then broadcast along the lanes they are stored in, and all
    four dots are A.B or A.B^T — none contracts over its left operand's
    rows. acc_refs (dk, dv): the f32 sums between q grid steps; none
    where one q block sees every kv column. (Where it does not — causal
    with Tk > Tq — kv blocks wholly above the diagonal run nothing, and
    the sums, zeroed at the first step, are what writes their zeros.)
    `group` query heads on this key/value head: the grid walks them on
    one more axis inside the kv block's, and the sums hold all of them."""
    window = band and band[0]
    ik = pl.program_id(2)
    iq = pl.program_id(3 + (group > 1))
    nq = pl.num_programs(3 + (group > 1))
    first, last = iq == 0, iq == nq - 1
    if group > 1:
        first &= pl.program_id(3) == 0
        last &= pl.program_id(3) == group - 1
    tq, tk = tiles
    d = q_ref.shape[-1] // heads
    offset = _block_offset(iq, ik, block_q, block_k, lone)

    if acc_refs:
        @pl.when(first)
        def _init():
            for ref in acc_refs:
                ref[:] = jnp.zeros_like(ref)

    def _body(where):
        q32 = _f32(q_ref)                           # [bq, w], for ds^T.q
        do32 = _f32(do_ref)                         # for p^T.do
        for j in range(block_k // tk):
            cols = slice(j * tk, (j + 1) * tk)
            ext = _extent(where, j * tk, tk, tq, block_q // tq, False,
                          window)
            if ext is None:
                assert acc_refs
                continue
            lo, hi = ext[:2]
            valid = functools.cache(lambda shape, row, part: _edges_valid(
                _distance(where, offset) + row, j * tk, shape, part, window,
                False))
            dks, dvs = [], []
            for h in range(heads):
                st = core.score_dot(_head_lanes(k_ref[0, cols, :], h, d),
                                    q_ref[0, lo:hi, :], scale)
                st = _mask_crossed(st, ext, valid)  # [tk, hi - lo]
                pt = jnp.exp(st - lse_ref[h, :, lo:hi])
                dpt = jax.lax.dot_general(
                    _head_lanes(v_ref[0, cols, :], h, d),
                    do_ref[0, lo:hi, :], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                dst = pt * (dpt - delta_ref[h, :, lo:hi])
                for parts, a, b in ((dks, dst, q32), (dvs, pt, do32)):
                    parts.append(jax.lax.dot_general(
                        a, b[lo:hi], (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))  # [tk, w]
            dk = _join_heads(dks, d) * jnp.float32(scale)
            dv = _join_heads(dvs, d)
            if acc_refs:
                acc_refs[0][cols] += dk
                acc_refs[1][cols] += dv
            else:
                dk_ref[0, cols, :] = dk.astype(dk_ref.dtype)
                dv_ref[0, cols, :] = dv.astype(dv_ref.dtype)

    _on_diagonal_position(causal, offset, block_q, block_k, _body, band)

    if acc_refs:
        @pl.when(last)
        def _fin():
            dk_ref[0] = acc_refs[0][:].astype(dk_ref.dtype)
            dv_ref[0] = acc_refs[1][:].astype(dv_ref.dtype)


def _fold(x, heads):
    """[B, T, H*D] -> [B*H, T, D]: every head a batch row of its own."""
    B, T = x.shape[:2]
    x = x.reshape(B, T, heads, -1)
    return jnp.swapaxes(x, 1, 2).reshape(B * heads, T, x.shape[-1])


def _unfold(x, heads):
    """[B*H, T, D] -> [B, T, H*D]."""
    BH, T, D = x.shape
    x = x.reshape(BH // heads, heads, T, D)
    return jnp.swapaxes(x, 1, 2).reshape(BH // heads, T, heads * D)


def _in_kernel_layout(heads, xs):
    """(arrays, H, g, group, back) for q-like [B, T, H*D] and kv-like
    [B, T, KVH*D] arrays `xs`, heads = (H, KVH): as they lie, g heads to
    a lane block, where attention_core.heads_per_block finds a block
    that picks heads out of the lanes — `group` = H / KVH query heads to
    a key/value head, which the entry allows over 1 only where g = 1;
    else with the heads FOLDED into the batch ([B*H, T, D]: one head,
    whose block is the whole minor dimension), which costs a transposing
    copy of every array each way. back() returns a [.., T, heads * D]
    result of the kernels to [B, T, heads * D]."""
    H, KVH = heads
    g = core.heads_per_block(H, xs[0].shape[-1] // H)
    if g is None:
        return ([_fold(x, H) for x in xs], 1, 1, 1,
                functools.partial(_unfold, heads=H))
    return xs, H, g, H // KVH, lambda x: x


def _index_maps(groups, causal, blocks, n_q, kv_major=False, window=None,
                group=1):
    """(row, col, stat) index maps over the grid (batch, head group, q
    block, kv block) — dkv's grid, `kv_major`, has the last two swapped:
    blocks of q-like arrays [B, Tq, H*D], of kv-like ones, and of the
    row statistics [B*H, 1, Tq], whose head is a row of the first axis.
    A grid step the causal mask leaves nothing of runs nothing, and
    should move nothing either: along the grid's inner axis its index
    is held at the nearest block that IS computed
    (attention_core.last_kv_block / first_q_block and, inside a band of
    `window`, their twins first_kv_block / last_q_block), and the
    pipeline starts no copy for an index that did not change.
    `group` query heads share a key/value head: the kv-like arrays are
    [B, Tk, H/group * D] and their head is `head // group`; dkv's grid
    walks the key/value heads, with the group's query heads on one more
    axis before the q blocks': (batch, kv head, kv block, member, q
    block)."""
    bq, bk = blocks.block_q, blocks.block_k
    if not causal:
        seen = lambda i, j: (i, j)
    elif kv_major:
        def seen(i, j):
            i = jax.lax.max(i, core.first_q_block(j, bq, bk, n_q))
            if window:
                i = jax.lax.min(i, core.last_q_block(j, bq, bk, n_q, window))
            return i, j
    else:
        def seen(i, j):
            j = jax.lax.min(j, core.last_kv_block(i, bq, bk))
            if window:
                j = jax.lax.max(j, core.first_kv_block(i, bq, bk, window))
            return i, j

    n = np.int32(group)       # Mosaic takes no i64, and x64 mode is on

    def over_grid(f):
        g = lambda b, h, i, j: f(b, h, jax.lax.div(h, n) if group > 1 else h,
                                 *seen(i, j))
        if kv_major and group > 1:
            return lambda b, kvh, j, r, i: g(b, kvh * n + r, i, j)
        return (lambda b, h, j, i: g(b, h, i, j)) if kv_major else g
    return (over_grid(lambda b, h, kvh, i, j: (b, i, h)),
            over_grid(lambda b, h, kvh, i, j: (b, j, kvh)),
            over_grid(lambda b, h, kvh, i, j: (b * groups + h, I0, i)))


def _band(window, t_q, t_k, blocks):
    """The kernels' `band`: (window, the distances at which a grid block
    is crossed by an edge of the band, whether any block lies wholly
    inside it); None without a window."""
    if window is None:
        return None
    return (window,) + core.band_offsets(
        t_q, t_k, blocks.block_q, blocks.block_k, window)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, heads, causal, window, scale, interpret):
    """q [B, Tq, H*D], k, v [B, Tk, KVH*D] -> out [B, Tq, H*D], all as
    the model holds them: the residual a remat policy saves is the value
    the model consumes. `heads` = (H, KVH)."""
    return _flash_fwd_impl(q, k, v, heads, causal, window, scale,
                           interpret)[0]


def _flash_fwd_impl(q, k, v, heads, causal, window, scale, interpret):
    """out [B, Tq, H*D], lse [B*H, 1, Tq]."""
    (q, k, v), H, g, group, back = _in_kernel_layout(heads, (q, k, v))
    B, Tq, HD = q.shape
    Tk, w = k.shape[1], HD // H * g
    blocks = core.choose_flash_blocks(Tq, Tk, HD // H)
    bq, bk = blocks.block_q, blocks.block_k
    row, col, stat = _index_maps(H // g, causal, blocks, Tq // bq,
                                 window=window, group=group)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, tiles=blocks.fwd,
                          heads=g, lone=(Tq, Tk) == (bq, bk),
                          band=_band(window, Tq, Tk, blocks)),
        grid=(B, H // g, Tq // bq, Tk // bk),
        in_specs=[pl.BlockSpec((1, bq, w), row),
                  pl.BlockSpec((1, bk, w), col),
                  pl.BlockSpec((1, bk, w), col)],
        out_specs=[pl.BlockSpec((1, bq, w), row),
                   pl.BlockSpec((g, 1, bq), stat)],
        out_shape=[
            jax.ShapeDtypeStruct((B, Tq, HD), q.dtype),
            # lse kept [B*H, 1, Tq]: trailing block dims (1, bq) satisfy
            # the TPU (8, 128) tiling rule, which [B*H, Tq] cannot
            jax.ShapeDtypeStruct((B * H, 1, Tq), jnp.float32),
        ],
        # the online softmax between kv grid steps; one step holds none
        scratch_shapes=[pltpu.VMEM((g, bq, 1), jnp.float32),
                        pltpu.VMEM((g, bq, 1), jnp.float32),
                        pltpu.VMEM((g, bq, w), jnp.float32)] * (Tk > bk),
        name="flash_attention_fwd",
        interpret=interpret,
    )(q, k, v)
    return back(out), lse


def _flash_fwd(q, k, v, heads, causal, window, scale, interpret):
    out, lse = _flash_fwd_impl(q, k, v, heads, causal, window, scale,
                               interpret)
    # named save points: a caller's remat policy that saves "flash_out"
    # and "flash_lse" keeps this kernel out of its backward pass. The
    # PRIMAL comes from the named value too: tagged only as a residual,
    # the layer's own recomputation asks for `out` again (the next
    # matmul's weight gradient needs its input) and runs the kernel twice
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (q, k, v, out, lse)


def _flash_bwd(heads, causal, window, scale, interpret, res, dout):
    *acts, lse = res
    (q, k, v, out, dout), H, g, group, back = _in_kernel_layout(
        heads, (*acts, dout))
    B, Tq, HD = q.shape
    Tk, w = k.shape[1], HD // H * g
    blocks = core.choose_flash_blocks(Tq, Tk, HD // H)
    bq, bk = blocks.block_q, blocks.block_k
    kernel = dict(scale=scale, causal=causal, block_q=bq, block_k=bk,
                  heads=g, lone=(Tq, Tk) == (bq, bk),
                  band=_band(window, Tq, Tk, blocks))
    stats = jax.ShapeDtypeStruct((B * H, 1, Tq), jnp.float32)
    maps = dict(window=window, group=group)

    row, col, stat = _index_maps(H // g, causal, blocks, Tq // bq, **maps)
    dq, delta = pl.pallas_call(
        functools.partial(_dq_kernel, tiles=blocks.dq, **kernel),
        grid=(B, H // g, Tq // bq, Tk // bk),
        in_specs=[pl.BlockSpec((1, bq, w), row),
                  pl.BlockSpec((1, bk, w), col),
                  pl.BlockSpec((1, bk, w), col),
                  pl.BlockSpec((1, bq, w), row),
                  pl.BlockSpec((1, bq, w), row),
                  pl.BlockSpec((g, 1, bq), stat)],
        out_specs=[pl.BlockSpec((1, bq, w), row),
                   pl.BlockSpec((g, 1, bq), stat)],
        out_shape=[jax.ShapeDtypeStruct((B, Tq, HD), q.dtype), stats],
        scratch_shapes=[pltpu.VMEM((bq, w), jnp.float32)] * (Tk > bk),
        name="flash_attention_dq",
        interpret=interpret,
    )(q, k, v, dout, out, lse)

    row, col, stat = _index_maps(H // g, causal, blocks, Tq // bq,
                                 kv_major=True, **maps)
    # a key/value head's `group` query heads: one more grid axis inside
    # the kv block's, its gradients summed in the kernel's f32 scratch
    members = (group,) * (group > 1)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, tiles=blocks.dkv, group=group,
                          **kernel),
        grid=(B, H // g // group, Tk // bk, *members, Tq // bq),
        in_specs=[pl.BlockSpec((1, bq, w), row),
                  pl.BlockSpec((1, bk, w), col),
                  pl.BlockSpec((1, bk, w), col),
                  pl.BlockSpec((1, bq, w), row),
                  pl.BlockSpec((g, 1, bq), stat),
                  pl.BlockSpec((g, 1, bq), stat)],
        out_specs=[pl.BlockSpec((1, bk, w), col),
                   pl.BlockSpec((1, bk, w), col)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, w), jnp.float32),
                        pltpu.VMEM((bk, w), jnp.float32)] * (
                            Tq > bq or group > 1
                            or (causal and Tk > Tq)),
        name="flash_attention_dkv",
        interpret=interpret,
    )(q, k, v, dout, lse, delta)
    return back(dq), back(dk), back(dv)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_arrays(q, k, v, causal=False, scale=None,
                           interpret=False, window=None):
    """Array-level entry: q [B, T, H, D], k, v [B, T, KVH, D] → out
    [B, T, H, D]; query head i attends key/value head i // (H / KVH).
    `window`: a query sees a key only if query - key < window (with
    `causal`, on equal lengths; a window the sequence fits in is none).
    Which layout the kernels get is counted as it traces
    (profiler.monitor `flash.calls.direct`, with `.g<heads a block>`, or
    `flash.calls.folded`; `flash.calls.gqa` where they get fewer
    key/value heads; `flash.calls.window`, with the share of a causal
    walk's tiles the band's walk visits observed in
    `flash.window.visited_share`, in %) and names the scope its ops
    stand under, with `.kv<key/value heads>` and `.w<window>` after it
    where the call has them."""
    from ...profiler import monitor
    B, Tq, H, D = q.shape
    Tk, KVH = k.shape[1:3]
    if H % KVH:
        raise ValueError(f"{H} query heads on {KVH} key/value heads")
    if window is not None:
        if not causal or Tq != Tk or window < 1:
            raise ValueError("a window takes causal attention on equal "
                             f"lengths (causal={causal}, {Tq} x {Tk}, "
                             f"window={window})")
        window = None if window >= Tk else int(window)
    scale = core.default_scale(scale, D)
    g = core.heads_per_block(H, D)
    if KVH != H and g != 1:
        # no lane block picks a key/value head for several query heads
        # side by side: every query head gets its own copy
        k, v = (jnp.repeat(x, H // KVH, axis=2) for x in (k, v))
        KVH = H
    path = "folded" if g is None else "direct"
    monitor.counter(f"flash.calls.{path}").inc()
    if g is not None:
        monitor.counter(f"flash.calls.direct.g{g}").inc()
    if KVH != H:
        monitor.counter("flash.calls.gqa").inc()
    if window is not None:
        monitor.counter("flash.calls.window").inc()
        monitor.histogram("flash.window.visited_share").observe(
            100.0 * core.window_visited_share(Tq, D, window))
    # the scope names the call in a trace and in the compile record's
    # `kernels` field: "flash.direct.kv4.w4096" has 4 key/value heads
    # under its query heads and a window of 4,096
    scope = f"flash.{path}" + (f".kv{KVH}" if KVH != H else "") \
        + (f".w{window}" if window is not None else "")
    with jax.named_scope(scope):
        out = _flash(*(x.reshape(*x.shape[:2], -1) for x in (q, k, v)),
                     (H, KVH), causal, window, scale, interpret)
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
                    partition=None, window=None):
    """Tensor-level entry used by F.scaled_dot_product_attention.
    `partition` = (mesh, batch_axis, head_axis), handed down by the
    builder of an auto-partitioned SPMD program
    (ops.kernels_partitioned_over), runs the kernel per shard of that
    mesh; None calls it bare."""
    from ...framework.core import apply_op
    interpret = core.default_interpret(interpret)

    def fn(qa, ka, va):
        call = lambda a, b, c: flash_attention_arrays(
            a, b, c, causal=causal, scale=scale, interpret=interpret,
            window=window)
        if partition is not None:
            call = _per_shard(call, *partition, qa.shape)
        return call(qa, ka, va)

    return apply_op(fn, q, k, v)
