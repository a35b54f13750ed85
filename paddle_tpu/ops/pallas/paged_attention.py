"""MXU-shaped ragged paged attention for TPU in Pallas.

The serving-side twin of flash_attention.py (PAPERS.md "Ragged Paged
Attention: A High-Performance and Flexible LLM Inference Kernel for
TPU"): ONE kernel call processes a batch of query tokens whose rows
belong to DIFFERENT sequences at DIFFERENT lengths — decode rows (one
token against a long history) and prefill-chunk rows (a slice of a
prompt against its own growing history) mix freely, under per-token
causal bounds.

Blocking (ops/pallas/attention_core.py owns the policy, shared with
the training kernel):

- tokens are grouped into Q-BLOCKS of `Bq` rows; for grouped-query
  models the `fold = H_q // H_kv` query heads sharing one kv head are
  folded into the row dimension, so every score dot is
  [Bq*fold, D] x [D, P] — M >= MIN_DOT_ROWS (target MXU_ROWS), where
  the seed-era kernel issued per-(token, head) [1, D] x [D, P] VPU
  dots. Rows of a q-block that don't own the current page are masked
  (and their probabilities explicitly zeroed), which costs nothing:
  they ride sublanes the narrow dot was wasting anyway.
- the kv pages each q-block must visit come from a host-side BLOCK
  PLAN (build_block_plan, grown in PagedKVCache.plan_ragged — no
  device round-trips in the serving scheduler): per q-block, the
  compacted list of (page id, owning row, kv start) slots any of its
  tokens' bounds reach, plus the real slot count. Shapes depend only
  on (T, B, W), so the serving executable's signature is unchanged.
- the page walk is DOUBLE-BUFFERED DMA (pallas_guide.md pattern): the
  kernel copies page i+1 into the alternate VMEM slot while computing
  page i, so the HBM walk overlaps the MXU work. A q-block of pure pad
  tokens has a zero slot count and issues NO copies at all. One copy
  brings a whole page — every kv head's [P, D] rows as one contiguous
  [P, H_kv*D] slab — and one program per q-block serves all kv heads
  from it (the chip's DMA refuses a single head's slice: it cuts inside
  the (8, 128) tile of the pool's last two dimensions).

The kernel still emits the per-token WORK counter (kv page blocks
actually computed = ceil(bound/P), 0 for pads) — the ground truth
behind the serving engine's `pad_token_fraction` metric and the tests'
skip-proof, not an estimate.

Softmax is the shared online/flash formulation in f32
(attention_core.softmax_update). On CPU (tier-1) the same kernel —
DMA double-buffering included — runs in Pallas interpret mode, so the
serving engine exercises identical code on every backend.
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import I0, I1, I2
from . import attention_core as core

__all__ = ["ragged_paged_attention", "ragged_work_plan",
           "build_block_plan"]


def build_block_plan(page_table, token_seq, bounds, page_size, q_block):
    """HOST-side (numpy) kv-page plan for the blocked kernel: which
    pages each q-block walks, compacted so the DMA loop touches only
    real work.

    Returns (blk_pages, blk_seq, blk_start, blk_n):

        blk_pages [QB, S] int32  page id of each slot (S = B*W cap)
        blk_seq   [QB, S] int32  page_table row owning the slot
        blk_start [QB, S] int32  kv position where the page starts
        blk_n     [QB]    int32  real slots; the kernel loops to this

    A slot exists when ANY token of the q-block has a causal bound
    reaching into that page (bound > page_start). Slots keep
    (row-major, page-minor) order; entries past blk_n are never read.
    Shapes are a pure function of (T, B, W, q_block), so a serving
    executable keyed on (T, B, W) stays one executable."""
    pt = np.asarray(page_table, np.int64)
    seq = np.asarray(token_seq, np.int64).reshape(-1)
    bd = np.asarray(bounds, np.int64).reshape(-1)
    B, W = pt.shape
    T = seq.shape[0]
    q_block = int(q_block)
    if T % q_block:
        raise ValueError(f"tokens {T} not divisible by q_block {q_block}")
    QB = T // q_block
    S = B * W
    # per-(q-block, row) max bound: the page reach of the block's rows
    bb = np.zeros((QB, B), np.int64)
    np.maximum.at(bb, (np.arange(T) // q_block, seq), bd)
    starts = np.arange(W, dtype=np.int64) * int(page_size)
    active = (bb[:, :, None] > starts[None, None, :]).reshape(QB, S)
    # stable partition: active slots first, (row, page) order preserved
    order = np.argsort(~active, axis=1, kind="stable")
    take = lambda a: np.take_along_axis(
        np.broadcast_to(a.reshape(1, S), (QB, S)), order, axis=1)
    return (take(pt.reshape(-1)).astype(np.int32),
            take(np.arange(S) // W).astype(np.int32),
            take((np.arange(S) % W) * int(page_size)).astype(np.int32),
            active.sum(axis=1).astype(np.int32))


def _block_plan_jnp(page_table, token_seq, bounds, page_size, q_block):
    """Traced twin of build_block_plan for callers without a host plan
    (eager tests, kernels jitted standalone): same fixed shapes, same
    slot order, computable on concrete OR traced arrays. The serving
    path never takes this — its plan rides in from plan_ragged."""
    pt = page_table.astype(jnp.int32)
    seq = token_seq.astype(jnp.int32).reshape(-1)
    bd = bounds.astype(jnp.int32).reshape(-1)
    B, W = pt.shape
    T = seq.shape[0]
    QB = T // int(q_block)
    S = B * W
    qb_idx = jnp.arange(T, dtype=jnp.int32) // jnp.int32(q_block)
    bb = jnp.zeros((QB, B), jnp.int32).at[qb_idx, seq].max(bd)
    slot = jnp.arange(S, dtype=jnp.int32)
    rows, pages = slot // W, slot % W
    starts = pages * jnp.int32(page_size)
    active = bb[:, rows] > starts[None, :]                   # [QB, S]
    # stable partition via a composite sort key (inactive rank S floats
    # every active slot ahead while the +slot term keeps their order)
    order = jnp.argsort(
        jnp.where(active, jnp.int32(0), jnp.int32(S)) * S + slot, axis=1)
    take = lambda a: jnp.take_along_axis(
        jnp.broadcast_to(a[None, :], (QB, S)), order, axis=1)
    return (take(pt.reshape(-1)), take(rows), take(starts),
            jnp.sum(active.astype(jnp.int32), axis=1))


def _kernel(bn_ref,                              # scalar prefetch
            plan_ref,                            # [3, S] SMEM window
            seq_ref, bd_ref, q_ref,              # blocked VMEM inputs
            k_hbm, v_hbm,                        # full pools (HBM)
            o_ref, w_ref,                        # blocked outputs
            kbuf, vbuf, sem,                     # DMA double buffers
            m_scr, l_scr, acc_scr,               # per-head softmax state
            *, page_size, scale):
    """One q-block program: walk the block's planned kv pages through
    the double buffer — each page ONE contiguous [P, H_kv*D] copy that
    serves every kv head — and online-softmax it into each head's
    folded [M, D] accumulator under the per-row bounds. Rows are
    (token, group-head) pairs already folded by the wrapper, so every
    tile is 2-D and (8, 128)-tileable. Every index that reaches a DMA
    slice is int32 — the package runs with x64 on, and Mosaic refuses
    an i64 memref_slice operand."""
    qb = pl.program_id(0)
    n = bn_ref[qb]
    KVH, M, D = q_ref.shape
    seq = seq_ref[...]                            # [M, 1] row per q row
    bd = bd_ref[...]                              # [M, 1] causal bounds
    m0, l0, acc0 = core.softmax_carry(M, D)
    for h in range(KVH):
        m_scr[h], l_scr[h], acc_scr[h] = m0, l0, acc0
    w_ref[...] = jnp.zeros((M, 1), jnp.int32)

    def copies(i, slot):
        page = plan_ref[0, i]
        return (pltpu.make_async_copy(k_hbm.at[page], kbuf.at[slot],
                                      sem.at[I0, slot]),
                pltpu.make_async_copy(v_hbm.at[page], vbuf.at[slot],
                                      sem.at[I1, slot]))

    @pl.when(n > 0)
    def _warmup():                                # first page's DMA
        for c in copies(I0, I0):
            c.start()

    def body(i, carry):
        slot = jax.lax.rem(i, I2)

        @pl.when(i + I1 < n)
        def _prefetch():                          # overlap: next page
            for c in copies(i + I1, jax.lax.rem(i + I1, I2)):
                c.start()

        for c in copies(i, slot):
            c.wait()
        b = plan_ref[1, i]
        start = plan_ref[2, i]
        pos = start + jax.lax.broadcasted_iota(
            jnp.int32, (M, page_size), 1)
        valid = (seq == b) & (pos < bd)           # shared by all heads
        kp = kbuf[slot].astype(jnp.float32)       # [P, H_kv*D]
        vp = vbuf[slot].astype(jnp.float32)
        for h in range(KVH):
            lanes = slice(h * D, (h + 1) * D)
            s = core.score_dot(q_ref[h].astype(jnp.float32),
                               kp[:, lanes], scale)   # [M, P]
            m_scr[h], l_scr[h], acc_scr[h] = core.softmax_update(
                m_scr[h], l_scr[h], acc_scr[h], s, vp[:, lanes],
                valid=valid)
        # measured work, not an estimate
        w_ref[...] += ((seq == b) & (start < bd)).astype(jnp.int32)
        return carry

    jax.lax.fori_loop(I0, n, body, I0)
    for h in range(KVH):
        out, _ = core.softmax_finalize(m_scr[h], l_scr[h], acc_scr[h])
        o_ref[h] = out.astype(o_ref.dtype)


def ragged_paged_attention(q, k_pages, v_pages, page_table, token_seq,
                           bounds, scale=None, interpret=None,
                           return_work=False, block_plan=None,
                           q_block=None):
    """Mixed prefill+decode attention over paged KV state.

    q:          [T, H, D]  query tokens, any mix of sequences/phases
    k_pages:    [n_pages, P, H_kv, D]  shared page pools (H_kv may
                divide H: grouped-query folding puts the group's heads
                in the same score dot)
    v_pages:    [n_pages, P, H_kv, D]
    page_table: [B, W] int32 page ids per sequence (pad page 0)
    token_seq:  [T] int32  page_table row of each token
    bounds:     [T] int32  kv tokens visible to each token (causal:
                history + preceding new tokens + itself); 0 marks a pad
                token that does NO work
    block_plan: optional (blk_pages, blk_seq, blk_start, blk_n) from
                build_block_plan — the serving path precomputes it on
                the host (PagedKVCache.plan_ragged); omitted, the same
                plan is derived in-trace.
    q_block:    rows per q-block; default
                attention_core.choose_ragged_q_block (M = q_block*fold
                <= MXU_ROWS and a multiple of the sublane tile).

    Layout the chip's tiling accepts for every head grouping: the
    wrapper folds q to [H_kv, T*fold, D] (token-major, group-head
    minor), so the kernel's q/out tile is the 2-D [M, D] slab of one kv
    head and the per-row sequence/bound/work columns are [M, 1] — M is
    a multiple of 8 (or the whole axis) whatever `fold` is. The three
    [QB, S] plan tables ride one [QB, 3, S] SMEM operand WINDOWED per
    q-block (only blk_n is scalar-prefetched), so SMEM holds one
    q-block's slots, not the whole plan.

    Returns [T, H, D] (and, with return_work, the per-token count of
    kv page blocks actually computed — ceil(bound/P), 0 for pads)."""
    T, H, D = q.shape
    n_pages, P, KVH, _ = k_pages.shape
    if H % KVH:
        raise ValueError(f"q heads {H} not a multiple of kv heads {KVH}")
    fold = H // KVH
    B, W = page_table.shape
    scale = core.default_scale(scale, D)
    interpret = core.default_interpret(interpret)
    bq = int(q_block) if q_block else core.choose_ragged_q_block(T, fold)
    if T % bq:
        raise ValueError(f"tokens {T} not divisible by q_block {bq}")
    QB = T // bq
    M = bq * fold
    if block_plan is None:
        block_plan = _block_plan_jnp(page_table, token_seq, bounds,
                                     P, bq)
    bp, bs, bst, bn = (jnp.asarray(a, jnp.int32) for a in block_plan)
    S = B * W
    if bp.shape != (QB, S) or bn.shape != (QB,):
        raise ValueError(
            f"block plan shape {bp.shape}/{bn.shape} does not match "
            f"q_block={bq} over T={T}, B={B}, W={W}")
    fold_col = lambda a: jnp.repeat(
        a.astype(jnp.int32).reshape(T), fold).reshape(T * fold, 1)
    qf = q.reshape(T, KVH, fold, D).transpose(1, 0, 2, 3).reshape(
        KVH, T * fold, D)
    col = pl.BlockSpec((M, 1), lambda qb, *_: (qb, I0))
    slab = pl.BlockSpec((KVH, M, D), lambda qb, *_: (I0, qb, I0))
    # a page is one contiguous [P, H_kv*D] slab of the pool (a free
    # reshape): slicing a single kv head out of HBM would cut inside
    # the (8, 128) tile, which the chip's DMA refuses
    pool = lambda a: a.reshape(n_pages, P, KVH * D)
    out, work = pl.pallas_call(
        functools.partial(_kernel, page_size=P, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(QB,),
            in_specs=[
                pl.BlockSpec((None, 3, S), lambda qb, *_: (qb, I0, I0),
                             memory_space=pltpu.SMEM),
                col, col, slab,
                # the pools stay in HBM; the kernel's double-buffered
                # DMA walks exactly the planned pages
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[slab, col],
            scratch_shapes=[
                pltpu.VMEM((2, P, KVH * D), k_pages.dtype),
                pltpu.VMEM((2, P, KVH * D), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((KVH, M), jnp.float32),     # running max
                pltpu.VMEM((KVH, M), jnp.float32),     # running sum
                pltpu.VMEM((KVH, M, D), jnp.float32),  # accumulators
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((KVH, T * fold, D), q.dtype),
            jax.ShapeDtypeStruct((T * fold, 1), jnp.int32),
        ],
        name="ragged_paged_attention",
        interpret=interpret,
    )(bn, jnp.stack([bp, bs, bst], axis=1),
      fold_col(token_seq), fold_col(bounds), qf,
      pool(k_pages), pool(v_pages))
    out = out.reshape(KVH, T, fold, D).transpose(1, 0, 2, 3).reshape(
        T, H, D)
    if return_work:
        return out, work.reshape(T, fold)[:, 0]
    return out


def ragged_work_plan(bounds, page_size):
    """Host-side mirror of the kernel's work counter: kv blocks each
    token will compute (ceil(bound/P); 0 for pads). The serving engine
    uses this to report `pad_token_fraction` without reading the work
    output back per step."""
    b = np.asarray(bounds, np.int64)
    return -(-b // int(page_size)) * (b > 0)
