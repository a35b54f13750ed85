"""Ring attention: sequence-parallel exact attention over the 'sp' axis.

Long-context design (SURVEY.md §6): the sequence dimension is sharded
across devices; each device keeps its Q shard resident and the K/V shards
rotate around the ring via lax.ppermute, one hop per step. Per-hop partial
attention results are merged with the online-softmax rule using each hop's
logsumexp — numerically identical to full attention while never
materializing more than one K/V shard per device. Compute per hop uses the
Pallas flash kernel on TPU (or the reference composition in tests).

Causality over a ring: the KV shard visiting at hop h originates from
device (my_idx - h) mod n. A query block attends to it fully when the
source index is smaller, causally when equal, not at all when larger.
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from jax import shard_map
from jax.sharding import PartitionSpec as P

__all__ = ["ring_attention", "ring_attention_arrays"]


def _chunk_attn(q, k, v, scale, mode):
    """Partial attention of q vs one kv chunk → (out, lse).
    q,k,v: [B, T, H, D]; mode: 0=skip, 1=causal, 2=full (traced scalar)."""
    qh = jnp.swapaxes(q, 1, 2).astype(jnp.float32)  # B,H,Tq,D
    kh = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vh = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * scale
    Tq, Tk = s.shape[-2], s.shape[-1]
    causal_mask = jnp.tril(jnp.ones((Tq, Tk), bool), k=Tk - Tq)
    allow = jnp.where(mode == 1, causal_mask,
                      jnp.full((Tq, Tk), True))
    allow = allow & (mode != 0)
    s = jnp.where(allow, s, -1e30)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vh)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    # fully-masked rows → lse=-inf, out=0
    out = out / jnp.maximum(l, 1e-30)[..., None]
    return jnp.swapaxes(out, 1, 2), lse  # [B,Tq,H,D], [B,H,Tq]


def ring_attention_arrays(q, k, v, mesh, axis="sp", causal=True,
                          scale=None):
    """q,k,v: [B, T_global, H, D] arrays sharded over `axis` on dim 1.
    Returns attention output with the same sharding."""
    D = q.shape[-1]
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    n = mesh.shape[axis]

    def spmd(q_loc, k_loc, v_loc):
        my = lax.axis_index(axis)
        perm = [(i, (i + 1) % n) for i in range(n)]

        # unrolled loop over ring hops (n is static); per-hop partial
        # results merge afterwards via their logsumexps
        kc, vc = k_loc, v_loc
        outs = []
        lses = []
        for h in range(n):
            src = (my - h) % n
            if causal:
                mode = jnp.where(src == my, 1, jnp.where(src < my, 2, 0))
            else:
                mode = jnp.full((), 2)
            out_h, lse_h = _chunk_attn(q_loc, kc, vc, scale, mode)
            outs.append(out_h)
            lses.append(lse_h)
            if h < n - 1:
                kc = lax.ppermute(kc, axis, perm)
                vc = lax.ppermute(vc, axis, perm)
        lse_stack = jnp.stack(lses)            # [n, B, H, Tq]
        m_all = jnp.max(lse_stack, axis=0)
        w = jnp.exp(lse_stack - m_all[None])   # [n, B, H, Tq]
        w_sum = jnp.sum(w, axis=0)
        out_stack = jnp.stack(outs)            # [n, B, Tq, H, D]
        w_b = jnp.moveaxis(w, 2, 3)[..., None]  # [n, B, Tq, H, 1]
        merged = jnp.sum(out_stack * w_b, axis=0) / jnp.maximum(
            jnp.moveaxis(w_sum, 1, 2)[..., None], 1e-30)
        return merged.astype(q_loc.dtype)

    # batch/head dims ride whatever other mesh axes exist (dp on batch,
    # mp on heads) so the ring composes inside a fleet hybrid step
    # without forcing an all-gather of the dp/mp shards
    def _axis_if(name, dim_size):
        return name if (name in mesh.axis_names
                        and mesh.shape[name] > 1
                        and dim_size % mesh.shape[name] == 0) else None

    b_ax = _axis_if("dp", q.shape[0])
    h_ax = _axis_if("mp", q.shape[2])
    spec = P(b_ax, axis, h_ax, None)
    return shard_map(spmd, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)


def ring_attention(q, k, v, mesh=None, axis="sp", causal=True, scale=None):
    """Tensor-level entry."""
    from ..framework.core import apply_op
    from ..distributed.env import get_mesh
    mesh = mesh or get_mesh()
    return apply_op(
        lambda qa, ka, va: ring_attention_arrays(qa, ka, va, mesh, axis,
                                                 causal, scale), q, k, v)
