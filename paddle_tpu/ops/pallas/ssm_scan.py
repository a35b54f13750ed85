"""Ragged selective-scan (Mamba SSM) Pallas kernel for TPU.

The recurrent twin of paged_attention.py (PAPERS.md "Compiler-First
State Space Duality and Portable O(1) Autoregressive Caching"): ONE
kernel call advances a batch of tokens whose rows belong to DIFFERENT
sequences — decode rows (one token) and prefill-chunk rows (a slice of
a prompt) mix freely in the same fixed-shape [T] token budget the
ragged attention step uses. Instead of walking kv pages, each token
updates its row's FIXED-SIZE state matrix h in [R, D, N] carried
through the scan:

    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * B_t) * x_t
    y_t = sum_N(h_t * C_t)

Ragged-batch mechanics:

- `token_seq[t]` names the state row token t belongs to; consecutive
  tokens of one row form its prefill chunk, scanned in order because
  the time loop is sequential anyway — no per-row segmentation needed.
- PAD tokens are neutralized by CONSTRUCTION, not masking: the caller
  zeroes `dt` on pads, so exp(0*A) = 1 and (0*B)*x = 0 — an identity
  state update. Pads may point at any row (slot 0 by convention)
  without corrupting it, which keeps the kernel free of a validity
  operand.
- the row select/merge uses a one-hot compare over the R rows instead
  of dynamic gather/scatter on the state: R is the serving batch width
  (small), and the compare vectorizes where a dynamic index would
  serialize through scalar memory.

The grid tiles the channel dimension D; B/C/token_seq are broadcast to
every tile and the [R, bd, N] state slab rides VMEM for the whole time
loop (N sits on the lane axis, so it pads to 128 lanes there: the
slab, not the activations, sets the tile size — choose_d_block). Shapes depend only on (T, R, D, N), so a serving executable
keyed on the fixed-shape step signature stays one executable. On CPU
(tier-1) the same kernel runs in Pallas interpret mode, so the serving
engine exercises identical code on every backend.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import I0
from . import attention_core as core

__all__ = ["ssm_scan", "selective_scan_reference", "choose_d_block",
           "scan_tile_bytes"]


_LANES = 128
# Mosaic's default scoped-VMEM limit on the v5e; a tile over it needs
# vmem_limit_bytes raised, and _VMEM_CEILING is as far as ssm_scan
# raises it (the core has 128 MiB of VMEM)
_VMEM_SCOPED_DEFAULT = 16 << 20
_VMEM_CEILING = 96 << 20


def scan_tile_bytes(bd, n_rows, n_tokens, d_state):
    """VMEM one grid tile holds, as the chip's compiler reports it
    (v5e, libtpu 0.0.34: 18.04 MB at bd=256, R=33, T=256, N=16; 17.00
    MB at bd=128, R=65): the [R, bd, N] state pads N up to 128 lanes
    and exists four times — h0 and h_out windows, each double-buffered
    — plus the double-buffered [T, bd] x/dt/y windows. All f32."""
    n_pad = -(-int(d_state) // _LANES) * _LANES
    state = 4 * int(n_rows) * bd * n_pad * 4
    acts = 2 * 3 * int(n_tokens) * bd * 4
    return state + acts


def choose_d_block(d_inner, n_rows, n_tokens, d_state,
                   budget=_VMEM_SCOPED_DEFAULT - (3 << 20)):
    """Channels per grid tile: the largest multiple of 128 that divides
    `d_inner` and whose tile (scan_tile_bytes) fits `budget`; the
    smallest such multiple when none fits (ssm_scan then raises the
    kernel's VMEM limit); the full width when no multiple of 128
    divides `d_inner` (the chip's tiling accepts a full dimension).
    Halving from d_inner missed these: 1536 -> 192 though 128 and 256
    divide it."""
    d_inner = int(d_inner)
    cands = [bd for bd in range(_LANES, d_inner + 1, _LANES)
             if d_inner % bd == 0]
    if not cands:
        return d_inner
    fit = [bd for bd in cands
           if scan_tile_bytes(bd, n_rows, n_tokens, d_state) <= budget]
    return max(fit) if fit else cands[0]


def _scan_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, seq_ref, h0_ref,
                 y_ref, h_out_ref, *, n_tokens):
    n_rows = h0_ref.shape[0]
    a = a_ref[:].astype(jnp.float32)               # [bd, N]
    h_init = h0_ref[:].astype(jnp.float32)         # [R, bd, N]

    def step(t, h):
        x_t = x_ref[pl.ds(t, 1), :].astype(jnp.float32)[0]    # [bd]
        dt_t = dt_ref[pl.ds(t, 1), :].astype(jnp.float32)[0]  # [bd]
        b_t = b_ref[pl.ds(t, 1), :].astype(jnp.float32)       # [1, N]
        c_t = c_ref[pl.ds(t, 1), :].astype(jnp.float32)       # [1, N]
        row = seq_ref[pl.ds(t, 1), :][0, 0]
        sel = (jax.lax.broadcasted_iota(jnp.int32, (n_rows, 1, 1), 0)
               == row)                                        # [R,1,1]
        h_row = jnp.sum(jnp.where(sel, h, jnp.float32(0.0)), axis=0)
        da = jnp.exp(dt_t[:, None] * a)                       # [bd, N]
        dbx = (dt_t * x_t)[:, None] * b_t                     # [bd, N]
        h_new = da * h_row + dbx
        y_t = jnp.sum(h_new * c_t, axis=-1)                   # [bd]
        y_ref[pl.ds(t, 1), :] = y_t[None, :].astype(y_ref.dtype)
        return jnp.where(sel, h_new[None, :, :], h)

    h_fin = jax.lax.fori_loop(0, n_tokens, step, h_init)
    h_out_ref[:] = h_fin.astype(h_out_ref.dtype)


def ssm_scan(x, dt, b, c, a, h0, token_seq, interpret=None):
    """Ragged selective scan over a fixed-shape token batch.

    Args:
        x [T, D]        post-conv activations (f32)
        dt [T, D]       softplus'd step sizes; MUST be zero on pad
                        tokens (identity update — see module doc)
        b [T, N]        input-projection coefficients B_t
        c [T, N]        output-projection coefficients C_t
        a [D, N]        state matrix A (negative; -exp(A_log))
        h0 [R, D, N]    per-row initial states (row 0 = pad slot)
        token_seq [T]   int32 owning row per token
        interpret       None = interpret everywhere but real TPU

    Returns (y [T, D], h_out [R, D, N]): per-token outputs
    y_t = sum_N(h_t * C_t) and every row's final state.
    """
    interpret = core.default_interpret(interpret)
    T, D = x.shape
    R, _, N = h0.shape
    bd = choose_d_block(D, R, T, N)
    # over the default limit the compiler keeps two more copies of the
    # state slab (24.96 MB reported where the formula says 17.0), so
    # ask for half as much again
    need = scan_tile_bytes(bd, R, T, N) + (2 << 20)
    if need > _VMEM_SCOPED_DEFAULT:
        need = need * 3 // 2
    if need > _VMEM_CEILING:
        raise ValueError(
            f"ssm_scan: {R} state rows x {bd} channels x {N} states "
            f"needs {need >> 20} MiB of VMEM per tile (ceiling "
            f"{_VMEM_CEILING >> 20} MiB) — lower the engine's max_batch")
    seq2d = token_seq.astype(jnp.int32).reshape(T, 1)
    y, h_out = pl.pallas_call(
        functools.partial(_scan_kernel, n_tokens=T),
        grid=(D // bd,),
        in_specs=[
            pl.BlockSpec((T, bd), lambda j: (I0, j)),
            pl.BlockSpec((T, bd), lambda j: (I0, j)),
            pl.BlockSpec((T, N), lambda j: (I0, I0)),
            pl.BlockSpec((T, N), lambda j: (I0, I0)),
            pl.BlockSpec((bd, N), lambda j: (j, I0)),
            # [T, 1]: 1D partial blocks trip XLA/Mosaic layout
            # disagreements on TPU; a trailing unit dim satisfies tiling
            pl.BlockSpec((T, 1), lambda j: (I0, I0)),
            pl.BlockSpec((R, bd, N), lambda j: (I0, j, I0)),
        ],
        out_specs=[
            pl.BlockSpec((T, bd), lambda j: (I0, j)),
            pl.BlockSpec((R, bd, N), lambda j: (I0, j, I0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, D), x.dtype),
            jax.ShapeDtypeStruct((R, D, N), h0.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(need, _VMEM_SCOPED_DEFAULT)),
        name="ssm_scan",
        interpret=interpret,
    )(x, dt, b, c, a, seq2d, h0)
    return y, h_out


def selective_scan_reference(x, dt, b, c, a, h0, token_seq):
    """Pure-jnp twin of `ssm_scan` (same ragged contract, same
    pad-by-zero-dt convention) — the equality oracle the kernel tests
    diff against, and nothing else imports it."""
    T, D = x.shape
    R = h0.shape[0]

    def step(h, inputs):
        x_t, dt_t, b_t, c_t, row = inputs
        sel = (jnp.arange(R, dtype=jnp.int32) == row)[:, None, None]
        h_row = jnp.sum(jnp.where(sel, h, jnp.float32(0.0)), axis=0)
        h_new = (jnp.exp(dt_t[:, None] * a) * h_row
                 + (dt_t * x_t)[:, None] * b_t[None, :])
        y_t = jnp.sum(h_new * c_t[None, :], axis=-1)
        return jnp.where(sel, h_new[None], h), y_t

    h_fin, ys = jax.lax.scan(
        step, h0.astype(jnp.float32),
        (x.astype(jnp.float32), dt.astype(jnp.float32),
         b.astype(jnp.float32), c.astype(jnp.float32),
         token_seq.astype(jnp.int32)))
    return ys.astype(x.dtype), h_fin.astype(h0.dtype)
