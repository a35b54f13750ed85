"""Attention functionals.

Parity: python/paddle/nn/functional/sparse_attention.py + the attention
core of python/paddle/nn/layer/transformer.py. On TPU the hot path is the
Pallas flash-attention kernel (paddle_tpu/ops/pallas/flash_attention.py);
this module exposes the framework-level API and uses the XLA
softmax(QK^T)V composition off the TPU backend (CPU tests), under
dropout or with an explicit mask.
"""
import math

import jax
import jax.numpy as jnp

from ...framework.core import Tensor, apply_op


def _sdpa_reference(q, k, v, mask=None, dropout_p=0.0, is_causal=False,
                    scale=None, window=None):
    # q: [B, T, H, D], k, v: [B, T, KVH, D] (paddle layout); query head
    # i attends key/value head i // (H / KVH)
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    group = q.shape[2] // k.shape[2]
    qh = jnp.swapaxes(q, 1, 2)  # B,H,T,D
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    if group > 1:
        kh, vh = (jnp.repeat(x, group, axis=1) for x in (kh, vh))
    logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh).astype(jnp.float32) * s
    if is_causal:
        Tq, Tk = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((Tq, Tk), bool), k=Tk - Tq)
        if window is not None:
            # a query sees a key only if query - key < window
            cm &= ~jnp.tril(jnp.ones((Tq, Tk), bool), k=Tk - Tq - window)
        logits = jnp.where(cm, logits, -1e30)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, -1e30)
        else:
            logits = logits + mask.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vh)
    return jnp.swapaxes(out, 1, 2)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, scale=None, name=None,
                                 window=None):
    """Flash attention on TPU; XLA reference composition elsewhere.

    Layout follows paddle incubate fused attention: [batch, seq, heads,
    dim]. `key` and `value` may have fewer heads than `query`, a divisor
    of its count (grouped-query attention: query head i attends key/value
    head i // group). `window`, with `is_causal` on equal lengths: a
    query sees a key only if query - key < window.
    """
    if window is not None and not is_causal:
        raise ValueError("window takes is_causal=True")
    from ...ops import flash_attention_available, flash_attention

    use_flash = (flash_attention_available() and dropout_p == 0.0
                 and attn_mask is None)
    if use_flash:
        return flash_attention(query, key, value, causal=is_causal,
                               scale=scale, window=window)

    def fn(q, k, v, *rest):
        m = rest[0] if rest else None
        return _sdpa_reference(q, k, v, m, dropout_p, is_causal, scale,
                               window)

    args = [query, key, value]
    if attn_mask is not None:
        args.append(attn_mask)
    return apply_op(fn, *args)


def rotary_embedding(x, theta=10000.0, position_ids=None, name=None):
    """Rotary position embedding (Su et al., 2021) over the whole last
    axis of x [batch, seq, ..., dim]: position t rotates the pair
    (x[i], x[i + dim/2]) — the two halves of the axis — by the angle
    t * theta^(-2i/dim). `position_ids` [seq] or [batch, seq] (default
    0..seq-1). Angles, sines and the rotation are float32; the result is
    in x's dtype. The tables are computed under the trace from the
    sequence length, so no maximum length is baked into a layer."""
    def fn(a, *rest):
        half = a.shape[-1] // 2
        inv = jnp.float32(theta) ** (
            -jnp.arange(half, dtype=jnp.float32) * 2.0 / (2 * half))
        pos = rest[0].astype(jnp.float32) if rest \
            else jnp.arange(a.shape[1], dtype=jnp.float32)
        ang = pos[..., None] * inv                  # [(batch,) seq, half]
        if ang.ndim == 2:
            ang = ang[None]
        ang = ang.reshape(ang.shape[:2] + (1,) * (a.ndim - 3) + (half,))
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        a32 = a.astype(jnp.float32)
        lo, hi = a32[..., :half], a32[..., half:]
        return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin],
                               axis=-1).astype(a.dtype)

    return apply_op(fn, x, *([position_ids] if position_ids is not None
                             else []))


def sparse_attention(query, key, value, sparse_csr_offset=None,
                     sparse_csr_columns=None, key_padding_mask=None,
                     attn_mask=None, name=None):
    """Block-sparse attention (reference: nn/functional/sparse_attention.py,
    CUDA-only there). TPU design: we compute dense flash attention with the
    sparsity pattern applied as a mask — XLA/Pallas tiles skip fully-masked
    blocks. CSR pattern is converted to a dense boolean mask."""
    if sparse_csr_offset is None:
        return scaled_dot_product_attention(query, key, value,
                                            attn_mask=attn_mask)

    def fn(q, k, v, off, cols):
        import jax
        T = q.shape[1]

        def row_mask(off_bh, cols_bh):
            # entry j lives in row r iff off[r] <= j < off[r+1]; invalid
            # tail entries (j >= nnz) are routed to row T and dropped by
            # the scatter's out-of-bounds rule. One vectorized scatter —
            # no host loop, works under jit.
            nnz = cols_bh.shape[0]
            j = jnp.arange(nnz)
            rows = jnp.searchsorted(off_bh.astype(jnp.int32), j,
                                    side="right") - 1
            rows = jnp.where(j < off_bh[-1], rows, T)
            return jnp.zeros((T, T), bool).at[rows, cols_bh].set(
                True, mode="drop")

        mask = jax.vmap(jax.vmap(row_mask))(off, cols)
        return _sdpa_reference(q, k, v, mask)
    return apply_op(fn, query, key, value, sparse_csr_offset,
                    sparse_csr_columns)
