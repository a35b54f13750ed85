"""Shared MXU blocking + online-softmax core for the attention kernels.

ONE module owns the block-shape policy and the flash/online-softmax
block update for both Pallas attention kernels (per *Ragged Paged
Attention*, arxiv 2604.15464: the serving and training kernels are the
same blocking with different gather patterns):

- ops/pallas/paged_attention.py — the ragged SERVING kernel: q-blocks
  of mixed prefill+decode tokens (heads folded into the row dimension
  for grouped-query models) against double-buffered kv pages;
- ops/pallas/flash_attention.py — the fused TRAINING kernel: q-blocks
  of one sequence's tokens against contiguous kv blocks, custom VJP;
  grid blocks in VMEM, taken in strips that compute only what the
  causal triangle leaves visible (choose_flash_blocks, causal_kv_tiles
  below).

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
import typing

import jax
import jax.numpy as jnp
import numpy as np

from .common import NEG_INF

# the MXU is a 128x128 systolic array: a score dot wants 128 query rows
MXU_ROWS = 128
# a register, and a tile in memory, is 128 lanes wide
LANES = 128
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


class FlashBlocks(typing.NamedTuple):
    """What choose_flash_blocks returns: the grid block the pipeline
    moves between HBM and VMEM, and per kernel (tq, tk): the strip its
    body takes at a time (tq rows in fwd and dq, tk columns in dkv) and
    the step of the visible extent along the other axis."""
    block_q: int
    block_k: int
    fwd: tuple
    dq: tuple
    dkv: tuple


# per kernel, the strip a body takes at a time (q rows, kv columns): the
# best of 128 / 256 / 512 by device time of the bare kernels on the v5e at
# [128, 1024, 64] and [32, 2048, 128] bf16 (PERF.md section 6, PR 26;
# tools/sweep_flash_tiles.py). The backward kernels are bound by the MXU,
# so the finest strip, which skips most, wins; the forward pays a softmax
# row statistic per strip
SUB_TILE_CAPS = {"fwd": (256, 256), "dq": (128, 128), "dkv": (128, 128)}


def sub_tile(block, cap):
    """Rows (or columns) of a sub-tile inside a grid block of `block`:
    the widest multiple of MXU_ROWS at most `cap` that divides it — a
    slice the chip's tiling takes at any offset — else the whole block
    as ONE tile (short or odd lengths: tier-1's T = 32, T = 1000)."""
    t = cap - cap % MXU_ROWS
    while t >= MXU_ROWS:
        if block % t == 0:
            return t
        t -= MXU_ROWS
    return block


def choose_flash_blocks(t_q, t_k, d):
    """Two-level blocking of the training kernel, a function of shapes
    alone. GRID block (block_q, block_k): what one grid step holds in
    VMEM — up to 1024 x 1024, so a step pays its DMA, init and finalize
    once per 1024 rows; halved down to divisors of the sequence lengths.
    block_k halves per doubling of the head dim beyond 64, as the k, v
    blocks and their f32 copies grow with d. bk seeds at a power of two
    so the halving lands on a divisor of a power-of-two t_k instead of
    collapsing to 1. SUB-TILE (tq, tk) per kernel: the forward and dq
    bodies take the q block in strips of tq rows, dkv takes the kv block
    in strips of tk columns; in a square block on the causal diagonal a
    strip computes only the part of the other axis that the triangle
    leaves visible, in steps of the other number (causal_kv_tiles /
    causal_q_tiles below) — so the f32 score tile is [tq, <= block_k],
    and about half the square is never computed."""
    bq = min(1024, t_q)
    while t_q % bq:
        bq //= 2
    seed = 1024 * 64 // max(d, 64)
    seed = 1 << (seed.bit_length() - 1)
    bk = min(seed, t_k)
    while t_k % bk:
        bk //= 2
    bq, bk = max(bq, 1), max(bk, 1)
    tiles = {name: (sub_tile(bq, cq), sub_tile(bk, ck))
             for name, (cq, ck) in SUB_TILE_CAPS.items()}
    return FlashBlocks(bq, bk, **tiles)


def heads_per_block(heads, d):
    """g: how many heads ONE lane block of a [B, T, heads * d] array
    holds, so that the training kernels pick heads by a BlockSpec index
    and the array needs no transpose — a function of the shape alone. A
    block's minor dimension must be a multiple of LANES or the whole
    array's: g = 1 where d is such a multiple (head dims 128, 256) or
    there is one head; LANES // d heads side by side where d divides
    LANES and g divides the head count (two at head dim 64); None where
    no block fits (head dims 80, 96; five heads of 64), and the caller
    folds the heads into the batch."""
    if heads == 1 or d % LANES == 0:
        return 1
    g = LANES // d
    return g if LANES % d == 0 and heads % g == 0 else None


def _tiles_in(x, t, n, up=False):
    """How many whole tiles of width t fit in x (`up`: are touched by
    x), held to [0, n]."""
    return min(max(x + (t - 1) * up, 0), n * t) // t


def causal_kv_tiles(row0, tq, tk, n):
    """(n_full, n_visit) for a strip of query rows [row0, row0 + tq)
    against the n kv tiles [j*tk, (j+1)*tk), Python ints, under the
    top-left-aligned causal mask (row >= column): tiles j < n_full hold
    no masked element, tiles n_full <= j < n_visit are crossed by the
    diagonal, the rest are wholly masked. The forward and dq kernels
    compute a strip against tiles [0, n_visit) and mask
    [n_full, n_visit): these ARE their extents."""
    return _tiles_in(row0 + 1, tk, n), _tiles_in(row0 + tq, tk, n, up=True)


def causal_q_tiles(col0, tk, tq, n):
    """(first, first_full) for a strip of kv columns [col0, col0 + tk)
    against the n q tiles [i*tq, (i+1)*tq): tiles i < first are wholly
    masked, first <= i < first_full are crossed by the diagonal, the
    rest hold no masked element — the dkv kernel's extents, the
    transpose of causal_kv_tiles."""
    return _tiles_in(col0, tq, n), _tiles_in(col0 + tk - 1, tq, n, up=True)


def window_kv_tiles(row0, tq, tk, n, window):
    """(first, first_full), causal_kv_tiles' twin for the band's
    TRAILING edge (a row sees a column only if row - column < window):
    of the n kv tiles, tiles j < first lie wholly behind the band of
    every row of the strip [row0, row0 + tq), first <= j < first_full
    are crossed by the edge, the rest hold nothing behind it."""
    return (_tiles_in(row0 - window + 1, tk, n),
            _tiles_in(row0 + tq - window, tk, n, up=True))


def window_q_tiles(col0, tk, tq, n, window):
    """(n_full, n_visit), causal_q_tiles' twin for the trailing edge:
    q tiles i < n_full see every column of the strip
    [col0, col0 + tk) inside the band, n_full <= i < n_visit are crossed
    by the edge, the rest lie wholly past it."""
    return (_tiles_in(col0 + window, tq, n),
            _tiles_in(col0 + tk - 1 + window, tq, n, up=True))


def last_kv_block(i, block_q, block_k):
    """The last kv GRID block that q grid block i sees under the causal
    mask (row >= column): the one its last row's own column lies in.
    For an index map: `i` is a traced int32 and so is every constant
    (the package runs in x64 mode, and Mosaic takes no i64)."""
    i32 = np.int32
    return jax.lax.div(i * i32(block_q) + i32(block_q - 1), i32(block_k))


def first_q_block(j, block_q, block_k, n_q):
    """The first q GRID block, of n_q, that sees kv grid block j under
    the causal mask: the one the block's first column's own row lies
    in; the last one where no q block sees it (Tk > Tq)."""
    i32 = np.int32
    return jax.lax.min(jax.lax.div(j * i32(block_k), i32(block_q)),
                       i32(n_q - 1))


def first_kv_block(i, block_q, block_k, window):
    """last_kv_block's lower twin: the first kv GRID block that q grid
    block i sees inside a band of `window` — the one its first row's
    oldest visible column lies in."""
    i32 = np.int32
    return jax.lax.div(
        jax.lax.max(i * i32(block_q) - i32(window - 1), i32(0)),
        i32(block_k))


def last_q_block(j, block_q, block_k, n_q, window):
    """first_q_block's upper twin: the last q GRID block, of n_q, that
    sees kv grid block j inside a band of `window` — the one the last
    row that sees the block's last column lies in."""
    i32 = np.int32
    return jax.lax.min(
        jax.lax.div((j + i32(1)) * i32(block_k) + i32(window - 2),
                    i32(block_q)), i32(n_q - 1))


def band_offsets(t_q, t_k, block_q, block_k, window):
    """(distances, inside) for the grid blocks of a [t_q, t_k] causal
    band of `window`: the distances first row - first column, Python
    ints, at which a block is CROSSED by one of the band's edges (or
    both) — neither wholly inside it nor wholly outside; the training
    kernels build one body per such distance, its strips' extents
    static — and whether any block lies wholly inside."""
    crossed, inside = set(), False
    for i in range(t_q // block_q):
        for j in range(t_k // block_k):
            o = i * block_q - j * block_k
            if o + block_q - 1 < 0 or o - (block_k - 1) >= window:
                continue
            if o - (block_k - 1) >= 0 and o + block_q - 1 < window:
                inside = True
            else:
                crossed.add(o)
    return tuple(sorted(crossed)), inside


def visited_tile_share(t_q, t_k, tiles, causal, window=None):
    """Share of the [t_q, t_k] score matrix's (tq, tk) sub-tiles that
    the kernels compute: 1.0 without a mask; under the causal mask what
    causal_kv_tiles visits — (n + 1) / (2n) for n square tiles a side —
    less, inside a band of `window`, what window_kv_tiles leaves behind.
    Where the grid blocks are not square a causal walk skips by the
    block, and `tiles` is the grid block."""
    tq, tk = tiles
    nq, nk = t_q // tq, t_k // tk
    if not causal:
        return 1.0
    visited = sum(
        max(causal_kv_tiles(i * tq, tq, tk, nk)[1] - (
            window_kv_tiles(i * tq, tq, tk, nk, window)[0]
            if window else 0), 0)
        for i in range(nq))
    return visited / float(nq * nk)


def window_visited_share(t, d, window):
    """Of the tiles a causal walk of the training kernels visits at
    [t, t] and head dim d, the share their walk of a band of `window`
    visits: the mean over the three kernels, each in its own strips
    (the band's walk) against the causal walk's unit (its strips where
    the grid block is square, else the block). What the pairs alone
    give is (window * (2t - window + 1)) / (t * (t + 1))."""
    b = choose_flash_blocks(t, t, d)
    block = (b.block_q, b.block_k)
    shares = []
    for tiles in (b.fwd, b.dq, b.dkv):
        walk = tiles if b.block_q == b.block_k else block
        shares.append(visited_tile_share(t, t, tiles, True, window)
                      / visited_tile_share(t, t, walk, True))
    return sum(shares) / len(shares)


def default_interpret(interpret):
    """The one interpret-mode switch: None means 'interpret everywhere
    but real TPU' — tier-1 CPU runs execute the identical kernel code
    TPU compiles."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def default_scale(scale, head_dim):
    return 1.0 / math.sqrt(head_dim) if scale is None else float(scale)


def softmax_carry(m_rows, d, dtype=jnp.float32, column=False):
    """Fresh (m, l, acc) accumulators for one q-block: running max,
    running sum, unnormalized output — f32 regardless of input dtype.
    `column`: m and l as [M, 1] (see _col), not [M]."""
    stat = (m_rows, 1) if column else (m_rows,)
    return (jnp.full(stat, NEG_INF, dtype), jnp.zeros(stat, dtype),
            jnp.zeros((m_rows, d), dtype))


def _col(x):
    """Row statistics against an [M, ...] tile: the serving kernel keeps
    them [M], the training kernel [M, 1] (the score tile's own layout,
    which costs no relayout against the tile)."""
    return x if x.ndim == 2 else x[:, None]


def softmax_update(m, l, acc, s, v, valid=None):
    """ONE online-softmax block update, shared by both kernels.

    m running max, l running sum (both [M] or both [M, 1]), acc [M, D]
    unnormalized accumulator; s [M, Bk] this block's raw scores
    (pre-mask); v [Bk, D] values. `valid` [M, Bk] masks scores out
    entirely — and, unlike plain NEG_INF substitution, zeroes p
    explicitly, so a row with NO valid column in this block (a ragged
    q-block row whose sequence doesn't own the kv page, a causal row
    above the block diagonal) contributes exactly nothing: m stays,
    alpha = 1, l and acc unchanged. NEG_INF is finite (-1e30), so exp
    never produces NaN even for rows nothing has touched yet.
    valid=None emits no iota, compare or select at all."""
    keep = m.ndim == 2
    if valid is not None:
        s = jnp.where(valid, s, jnp.float32(NEG_INF))
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=keep))
    p = jnp.exp(s - _col(m_new))
    if valid is not None:
        p = jnp.where(valid, p, jnp.float32(0.0))
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + jnp.sum(p, axis=1, keepdims=keep)
    acc_new = acc * _col(alpha) + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def softmax_finalize(m, l, acc):
    """(out [M, D], lse shaped as m) from the final carry. A row no
    block ever touched (bound-0 pad token) divides 0 by the floor and
    comes out exactly zero — garbage by construction, sliced off by the
    caller."""
    l_safe = jnp.maximum(l, jnp.float32(1e-30))
    return acc / _col(l_safe), m + jnp.log(l_safe)


def score_dot(q, k, scale):
    """The score dot both kernels emit: [M, D] x [D, Bk] in f32 on the
    MXU. `k` arrives [Bk, D] (page/block layout); the contraction is
    over D."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return s * jnp.float32(scale)


def _rows_ahead(shape, row_axis):
    """query row - kv column of every element of a `shape` tile whose
    first row and column are 0; rows run along `row_axis`. The same for
    every tile of a shape, so a mask costs one compare."""
    return (jax.lax.broadcasted_iota(jnp.int32, shape, row_axis)
            - jax.lax.broadcasted_iota(jnp.int32, shape, 1 - row_axis))


def window_valid(row0, col0, shape, window, row_axis=0):
    """causal_valid's twin for the band's trailing edge: query row -
    kv column < window."""
    return _rows_ahead(shape, row_axis) < window + col0 - row0


def causal_valid(row0, col0, shape, row_axis=0):
    """`shape` bool tile of the causal mask, query row >= kv column,
    for a tile whose first row and column stand at absolute positions
    row0, col0; rows run along `row_axis` (1 for the dkv kernel's
    transposed [columns, rows] tiles)."""
    return _rows_ahead(shape, row_axis) >= col0 - row0
