"""Shared MXU blocking + online-softmax core for the attention kernels.

ONE module owns the block-shape policy and the flash/online-softmax
block update for both Pallas attention kernels (per *Ragged Paged
Attention*, arxiv 2604.15464: the serving and training kernels are the
same blocking with different gather patterns):

- ops/pallas/paged_attention.py — the ragged SERVING kernel: q-blocks
  of mixed prefill+decode tokens (heads folded into the row dimension
  for grouped-query models) against double-buffered kv pages;
- ops/pallas/flash_attention.py — the fused TRAINING kernel: q-blocks
  of one sequence's tokens against contiguous kv blocks, custom VJP.

The policy both enforce: every score dot is [M, D] x [D, Bk] with
M >= MIN_DOT_ROWS (the f32 sublane tile — anything narrower leaves the
128x128 MXU computing mostly zeros; the seed-era serving kernel's
[1, D] x [D, P] per-token dots were the motivating offender), targeting
MXU_ROWS-row tiles when the token count allows.
tools/check_dot_shapes.py ratchets this by parsing the lowered kernels
rather than trusting the claim.

Both kernels run the SAME code in Pallas interpret mode on CPU (tier-1)
— `default_interpret` is the one switch.
"""
import math

import jax
import jax.numpy as jnp

from .common import NEG_INF

# the MXU is a 128x128 systolic array: a score dot wants 128 query rows
MXU_ROWS = 128
# f32 tiles are (8, 128): a dot with M < 8 pads the sublane dimension
# with zeros — the hard floor the dot-shape gate enforces
MIN_DOT_ROWS = 8
# serving pads token counts up to this so q-blocks always reach the
# floor (masked pad rows ride the same MXU tile for free)
MIN_Q_TOKENS = MIN_DOT_ROWS


def choose_q_block(n_tokens, cap=MXU_ROWS):
    """Rows per q-block: the largest divisor of `n_tokens` at most
    `cap`, found by halving (power-of-two token buckets land on `cap`
    exactly; an odd eager-call count runs as one block). Callers with
    folded heads pass cap=MXU_ROWS//fold so M = block * fold still
    targets one MXU tile."""
    bq = max(int(n_tokens), 1)
    cap = max(int(cap), 1)
    while bq > cap and bq % 2 == 0:
        bq //= 2
    return bq


def choose_ragged_q_block(n_tokens, fold=1):
    """Tokens per q-block of the ragged serving kernel for a model that
    folds `fold` query heads onto each kv head. The kernel's tiles are
    [M, D] with M = block * fold, so the block is choose_q_block under
    cap MXU_ROWS // fold, then DOUBLED until M is a multiple of the
    sublane tile (or the block is the whole token axis, which the
    chip's tiling accepts as a full dimension) — a head count that is
    not a power of two (20 query heads on one kv head) still yields a
    tileable M. The host planner and the kernel wrapper both call this,
    which is the whole shape contract between them."""
    n = max(int(n_tokens), 1)
    fold = max(int(fold), 1)
    bq = choose_q_block(n, cap=max(MXU_ROWS // fold, 1))
    while bq < n and (bq * fold) % MIN_DOT_ROWS and n % (2 * bq) == 0:
        bq *= 2
    return bq


def choose_flash_blocks(t_q, t_k, d):
    """(block_q, block_k) for the training kernel. Biggest blocks win
    decisively on real TPU (measured on [128, 1024, 64] bf16: 1024x1024
    runs fwd 1.9x / fwd+bwd 1.5x faster than 512x512; small bk is the
    worst axis to shrink). 1024x1024 puts the f32 [bq, bk] score+prob
    tiles at ~8 MB of VMEM — about the ceiling once q/k/v/do/acc tiles
    are added, so the cap is the VMEM budget; round down to divisors of
    the seq lens. The dkv backward holds ~3 concurrent f32 [bq, bk]
    tiles plus q/k/v/do tiles that scale with d — shrink bk for head
    dims > 64 to stay inside the same budget the d=64 measurement
    validated. bk seeds at a power of two so the halving loop lands on
    a divisor of a power-of-two t_k instead of collapsing to 1."""
    bq = min(1024, t_q)
    while t_q % bq:
        bq //= 2
    seed = 1024 * 64 // max(d, 64)
    seed = 1 << (seed.bit_length() - 1)
    bk = min(seed, t_k)
    while t_k % bk:
        bk //= 2
    return max(bq, 1), max(bk, 1)


def default_interpret(interpret):
    """The one interpret-mode switch: None means 'interpret everywhere
    but real TPU' — tier-1 CPU runs execute the identical kernel code
    TPU compiles."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def default_scale(scale, head_dim):
    return 1.0 / math.sqrt(head_dim) if scale is None else float(scale)


def softmax_carry(m_rows, d, dtype=jnp.float32):
    """Fresh (m, l, acc) accumulators for one q-block: running max,
    running sum, unnormalized output — f32 regardless of input dtype."""
    return (jnp.full((m_rows,), NEG_INF, dtype),
            jnp.zeros((m_rows,), dtype),
            jnp.zeros((m_rows, d), dtype))


def softmax_update(m, l, acc, s, v, valid=None):
    """ONE online-softmax block update, shared by both kernels.

    m [M] running max, l [M] running sum, acc [M, D] unnormalized
    accumulator; s [M, Bk] this block's raw scores (pre-mask); v
    [Bk, D] values. `valid` [M, Bk] masks scores out entirely — and,
    unlike plain NEG_INF substitution, zeroes p explicitly, so a row
    with NO valid column in this block (a ragged q-block row whose
    sequence doesn't own the kv page, a causal row above the block
    diagonal) contributes exactly nothing: m stays, alpha = 1, l and
    acc unchanged. NEG_INF is finite (-1e30), so exp never produces
    NaN even for rows nothing has touched yet."""
    if valid is not None:
        s = jnp.where(valid, s, jnp.float32(NEG_INF))
    m_new = jnp.maximum(m, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    if valid is not None:
        p = jnp.where(valid, p, jnp.float32(0.0))
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + jnp.sum(p, axis=1)
    acc_new = acc * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def softmax_finalize(m, l, acc):
    """(out [M, D], lse [M]) from the final carry. A row no block ever
    touched (bound-0 pad token) divides 0 by the floor and comes out
    exactly zero — garbage by construction, sliced off by the caller."""
    l_safe = jnp.maximum(l, jnp.float32(1e-30))
    return acc / l_safe[:, None], m + jnp.log(l_safe)


def score_dot(q, k, scale):
    """The score dot both kernels emit: [M, D] x [D, Bk] in f32 on the
    MXU. `k` arrives [Bk, D] (page/block layout); the contraction is
    over D."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return s * jnp.float32(scale)


def causal_valid(iq, ik, block_q, block_k):
    """[block_q, block_k] bool: query row >= kv column (absolute
    positions from the block indices) — the training kernel's mask."""
    rows = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return rows >= cols
