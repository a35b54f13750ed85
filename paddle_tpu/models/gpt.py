"""GPT model family — the flagship for BASELINE.json's headline config
("GPT-3 6.7B with fleet hybrid-parallel"). API mirrors PaddleNLP's GPT
(reference trains it via python/paddle/distributed/fleet); architecture is
TPU-first:

- pre-norm decoder blocks, bias-less where harmless, bf16-friendly
- attention through F.scaled_dot_product_attention → Pallas flash kernel
- shapes kept static & MXU-aligned (head_dim multiple of 128 advised)
- `parallel_config` marks how each weight shards over the fleet mesh
  (mp column/row, dp replicated) — consumed by distributed.fleet.
"""
import math
import os

import numpy as np
import jax.numpy as jnp

from ..framework.core import Tensor
from .. import nn
from ..nn import functional as F
from ..nn import initializer as I

__all__ = ["GPTConfig", "GPTModel", "GPTForCausalLM", "gpt_tiny",
           "gpt_small", "gpt_medium", "gpt_1p3b", "gpt_6p7b",
           "gpt_moe"]


class GPTConfig:
    def __init__(self, vocab_size=50304, hidden_size=768, num_layers=12,
                 num_heads=12, intermediate_size=None,
                 max_position_embeddings=1024, dropout=0.0,
                 layer_norm_epsilon=1e-5, initializer_range=0.02,
                 use_bias=True, scan_layers=True, scan_remat=False,
                 sequence_parallel=False, num_experts=0, moe_every=2,
                 moe_top_k=2, moe_capacity_factor=1.25):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.intermediate_size = intermediate_size or 4 * hidden_size
        self.max_position_embeddings = max_position_embeddings
        self.dropout = dropout
        self.layer_norm_epsilon = layer_norm_epsilon
        self.initializer_range = initializer_range
        self.use_bias = use_bias
        # scan_layers: under jit, run the homogeneous block stack as one
        # lax.scan over stacked per-layer params — the block is traced
        # and compiled ONCE instead of num_layers times (deep models
        # otherwise pay minutes of XLA compile). scan_remat wraps the
        # scan body in jax.checkpoint (recompute activations in backward).
        self.scan_layers = scan_layers
        self.scan_remat = scan_remat
        # sequence_parallel: shard the sequence dim over the 'sp' mesh
        # axis; attention runs as ring attention (K/V shards rotate via
        # ppermute, online-softmax merge) — exact, long-context capable
        self.sequence_parallel = sequence_parallel
        # num_experts > 0: every `moe_every`-th block swaps its MLP for
        # an expert-parallel MoELayer (experts shard over 'ep'); the
        # heterogeneous stack disables the scan-over-layers path
        self.num_experts = num_experts
        self.moe_every = moe_every
        self.moe_top_k = moe_top_k
        self.moe_capacity_factor = moe_capacity_factor


class StaticCacheSlot:
    """One layer's static KV cache: preallocated k/v [B, L, H, D] plus the
    write position (traced scalar). See GPTAttention._forward_static_cache."""

    __slots__ = ("k", "v", "pos")

    def __init__(self, k, v, pos):
        self.k = k
        self.v = v
        self.pos = pos


class PagedCacheSlot:
    """One layer's view of a shared PagedKVCache for continuous-batching
    decode: `cache` is the ops.paged_attention.PagedKVCache, `seq_ids`
    the batch rows, `views` the per-step (page_table, lengths)."""

    __slots__ = ("cache", "layer", "seq_ids", "views")

    def __init__(self, cache, layer, seq_ids, views):
        self.cache = cache
        self.layer = layer
        self.seq_ids = seq_ids
        self.views = views


class PagedJitSlot:
    """Traced twin of PagedCacheSlot for the fully-jitted decode step:
    one layer's k/v page pools (traced, donated by the caller) plus the
    host-planned write coordinates and read views (see
    PagedKVCache.plan_decode)."""

    __slots__ = ("k", "v", "pages", "in_pages", "pt", "lens")

    def __init__(self, k, v, pages, in_pages, pt, lens):
        self.k = k
        self.v = v
        self.pages = pages
        self.in_pages = in_pages
        self.pt = pt
        self.lens = lens


class RaggedJitSlot:
    """One layer's state for the fully-jitted RAGGED step (the mixed
    prefill+decode program over the Pallas kernel in
    ops/pallas/paged_attention.py): traced/donated k/v pools plus the
    host plan from PagedKVCache.plan_ragged — per-token scatter
    coordinates and causal bounds, per-row page tables, and the
    q-block kv-page walk (blk_*) the kernel's double-buffered DMA loop
    follows."""

    __slots__ = ("k", "v", "tok_pages", "tok_in_pages", "page_table",
                 "token_seq", "bounds", "blk_pages", "blk_seq",
                 "blk_start", "blk_n")

    def __init__(self, k, v, tok_pages, tok_in_pages, page_table,
                 token_seq, bounds, blk_pages=None, blk_seq=None,
                 blk_start=None, blk_n=None):
        self.k = k
        self.v = v
        self.tok_pages = tok_pages
        self.tok_in_pages = tok_in_pages
        self.page_table = page_table
        self.token_seq = token_seq
        self.bounds = bounds
        self.blk_pages = blk_pages
        self.blk_seq = blk_seq
        self.blk_start = blk_start
        self.blk_n = blk_n


def sample_token_rows(last, temps, top_ks, top_ps, rng_keys, positions):
    """On-device per-row sampling for the ragged serving step: one
    fixed-shape program covers every request's sampling config, so
    admit/evict (and mixed greedy/sampled batches) never change the
    compiled signature.

    last [B, V] next-token logits; temps [B] f32 (<= 0 selects the
    greedy argmax lane BIT-EXACTLY — the pre-sampling serving
    behavior); top_ks [B] i32 (0 disables); top_ps [B] f32 (1.0
    disables); rng_keys [B, 2] u32 per-SEQUENCE base PRNG keys;
    positions [B] i32 absolute position of each row's sampled token.

    The draw key is fold_in(base_key, position): a function of the
    request's seed and the token index ONLY — which batch the row
    landed in, what its neighbors were, or which ENGINE decoded it
    (prefill/decode disaggregation) cannot change the sample, so a
    handed-off chain decodes token-for-token equal to a single-engine
    run and a fixed seed reproduces exactly. Returns [B] int32."""
    import jax
    V = last.shape[-1]
    greedy = jnp.argmax(last, axis=-1).astype(jnp.int32)

    def _sampled(_):
        arr = last.astype(jnp.float32) \
            / jnp.maximum(temps[:, None], 1e-6)
        # per-row top-k: the kth-largest value is the row's floor
        # (k <= 0 keeps everything). One descending sort serves both
        # filters.
        # (stable=False: only the sorted VALUES are read, and the
        # chip's compiler takes twice as long over a stable sort —
        # 21 s against 10.5 s for [64, 50304] f32 on a v5e)
        srt = jnp.sort(arr, axis=-1, stable=False)[:, ::-1]
        k_eff = jnp.clip(jnp.where(top_ks > 0, top_ks, V), 1, V)
        kth = jnp.take_along_axis(srt, (k_eff - 1)[:, None], axis=-1)
        arr = jnp.where(arr < kth, jnp.float32(-1e30), arr)
        # per-row nucleus over the top-k-masked logits: keep the
        # smallest prefix of the sorted probs reaching top_p (a token
        # stays iff the mass BEFORE it is < top_p) — top_p = 1.0 keeps
        # every survivor
        srt2 = jnp.sort(arr, axis=-1, stable=False)[:, ::-1]
        p_srt = jax.nn.softmax(srt2, axis=-1)
        before = jnp.cumsum(p_srt, axis=-1) - p_srt
        keep = before < top_ps[:, None]
        thresh = jnp.min(jnp.where(keep, srt2, jnp.inf), axis=-1,
                         keepdims=True)
        arr = jnp.where(arr >= thresh, arr, jnp.float32(-1e30))
        step_keys = jax.vmap(jax.random.fold_in)(rng_keys, positions)
        sampled = jax.vmap(jax.random.categorical)(step_keys, arr)
        return jnp.where(temps <= 0.0, greedy,
                         sampled.astype(jnp.int32))

    # runtime branch, ONE executable: an all-greedy batch (the default
    # serving workload) skips the two [B, V] sorts + softmax/cumsum at
    # execution time instead of paying for a lane jnp.where would
    # force XLA to materialize; a mixed batch takes the sampled branch
    # and its greedy rows still ride the bit-exact argmax lane
    return jax.lax.cond(jnp.any(temps > 0.0), _sampled,
                        lambda _: greedy, None)


def sampling_key_data(seed):
    """Host-side uint32[2] PRNG key data for `seed` (the threefry key
    layout jax.random.PRNGKey produces) — no device op at submit."""
    seed = int(seed)
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                    np.uint32)


def _remat_policy(scan_remat):
    """Map cfg.scan_remat to a jax.checkpoint policy. True → full
    recompute (policy None). "dots" → save non-batch matmul outputs.
    "names" → save exactly the named points of a block and recompute
    the cheap rest: the qkv and ffn-up matmul outputs (gpt_qkv,
    gpt_ffn_in, tagged below) and the attention core's output — from
    the flash kernel the residuals it names inside its custom_vjp,
    flash_out ([B, T, H], the value out_proj consumes) and flash_lse
    ([B*heads, 1, T] float32), so the backward pass runs dq and dkv on
    what the forward made and the forward kernel runs once a layer;
    from ring attention gpt_attn_out (the plain composition, off the
    chip or under dropout, names nothing and is recomputed). 8*B*T*H
    bf16 + 4*B*heads*T bytes a block: 134.7 MB at 8 x 1024 x 1024 with
    16 heads. True and "dots" know no names and run the forward kernel
    again."""
    import jax
    if scan_remat == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    if scan_remat == "names":
        return jax.checkpoint_policies.save_only_these_names(
            "gpt_qkv", "flash_out", "flash_lse", "gpt_attn_out",
            "gpt_ffn_in")
    return None


def _ckpt_name(t, name):
    """Tag a traced activation as a named remat save point. No-op in
    eager mode (concrete arrays go through the tape; re-wrapping would
    orphan them from it)."""
    import jax
    if isinstance(t.value, jax.core.Tracer):
        from jax.ad_checkpoint import checkpoint_name
        return Tensor(checkpoint_name(t.value, name))
    return t


class GPTAttention(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        h, nh = cfg.hidden_size, cfg.num_heads
        self.num_heads = nh
        self.head_dim = h // nh
        w_init = nn.initializer.Normal(0.0, cfg.initializer_range)
        battr = None if cfg.use_bias else False
        self.qkv_proj = nn.Linear(h, 3 * h,
                                  weight_attr=w_init, bias_attr=battr)
        self.out_proj = nn.Linear(h, h, weight_attr=w_init, bias_attr=battr)
        self.dropout = cfg.dropout
        self.sequence_parallel = cfg.sequence_parallel

    def forward(self, x, cache=None):
        B, T, H = x.shape
        qkv = _ckpt_name(self.qkv_proj(x), "gpt_qkv")
        # three lane-aligned slices of [B, T, 3H], each then viewed by head
        # for free; cut out of a [B, T, 3, heads, head_dim] view they cost
        # a transposed copy of the whole of qkv each way on the chip
        from ..tensor.manipulation import split
        q, k, v = (t.reshape([B, T, self.num_heads, self.head_dim])
                   for t in split(qkv, 3, axis=-1))
        if isinstance(cache, StaticCacheSlot):
            return self._forward_static_cache(x, q, k, v, cache)
        if isinstance(cache, RaggedJitSlot):
            return self._forward_paged_ragged(x, q, k, v, cache)
        if isinstance(cache, PagedJitSlot):
            return self._forward_paged_jit(x, q, k, v, cache)
        if isinstance(cache, PagedCacheSlot):
            return self._forward_paged_cache(x, q, k, v, cache)
        if cache is not None:  # legacy growing (k, v) protocol
            from ..tensor.manipulation import concat
            k = concat([cache[0], k], axis=1)
            v = concat([cache[1], v], axis=1)
            cache = (k, v)
        if self.sequence_parallel and cache is None:
            from ..ops.ring_attention import ring_attention
            out = _ckpt_name(
                ring_attention(q, k, v, causal=True).reshape([B, T, H]),
                "gpt_attn_out")
        else:
            # the flash kernel names its own save points (flash_out,
            # flash_lse): naming the same bytes here would save them twice
            out = F.scaled_dot_product_attention(
                q, k, v, is_causal=True,
                dropout_p=self.dropout if self.training else 0.0
            ).reshape([B, T, H])
        out = self.out_proj(out)
        return (out, cache) if cache is not None else out

    def _forward_static_cache(self, x, q, k, v, cache):
        """Decode/prefill against a preallocated [B, L, H, D] KV buffer:
        write the T new keys/values at position `pos` (dynamic slice
        update), attend q over the full buffer with a `col <= pos + row`
        mask. Static shapes throughout, so generate() compiles exactly
        two programs (prefill + scanned decode) regardless of length —
        replaces the per-token concat that recompiled every step."""
        import jax
        B, T, H = x.shape
        kb, vb, pos = cache.k.value, cache.v.value, cache.pos
        kb = jax.lax.dynamic_update_slice(kb, k.value, (0, pos, 0, 0))
        vb = jax.lax.dynamic_update_slice(vb, v.value, (0, pos, 0, 0))
        L = kb.shape[1]
        scale = 1.0 / math.sqrt(self.head_dim)
        s = jnp.einsum("bthd,blhd->bhtl", q.value.astype(jnp.float32),
                       kb.astype(jnp.float32)) * scale
        cols = jnp.arange(L)[None, None, None, :]
        rows = jnp.arange(T)[None, None, :, None]
        s = jnp.where(cols <= pos + rows, s, jnp.float32(-1e30))
        p = jax.nn.softmax(s, axis=-1).astype(vb.dtype)
        out = jnp.einsum("bhtl,blhd->bthd", p, vb)
        out = self.out_proj(Tensor(out.reshape(B, T, H).astype(
            x.value.dtype)))
        return out, StaticCacheSlot(Tensor(kb), Tensor(vb), pos)


    def _forward_paged_jit(self, x, q, k, v, slot):
        """Traced decode step (T==1) over the paged pools: one batched
        scatter writes every sequence's new k/v row into its page, then
        one paged_attention gather reads each row's own history. All of
        it lives inside the caller's single jitted program."""
        from ..ops.paged_attention import paged_attention
        B, T, H = x.shape
        kd = slot.k.dtype
        slot.k = slot.k.at[slot.pages, slot.in_pages].set(
            k.value[:, 0].astype(kd))
        slot.v = slot.v.at[slot.pages, slot.in_pages].set(
            v.value[:, 0].astype(kd))
        out = paged_attention(q.value[:, 0], slot.k, slot.v, slot.pt,
                              slot.lens + 1)
        out = self.out_proj(Tensor(out.reshape(B, 1, H).astype(
            x.value.dtype)))
        return out, slot

    def _forward_paged_ragged(self, x, q, k, v, slot):
        """Traced RAGGED step over the paged pools: one batched scatter
        writes every token's k/v row into its planned (page, slot), then
        ONE Pallas ragged-paged-attention call reads each token's own
        history under its causal bound — decode rows and prefill chunks
        in the same program, pad tokens (bound 0) skipped outright."""
        from ..ops.pallas.paged_attention import ragged_paged_attention
        B, T, H = x.shape  # B == 1: the token axis carries the batch
        kd = slot.k.dtype
        slot.k = slot.k.at[slot.tok_pages, slot.tok_in_pages].set(
            k.value[0].astype(kd))
        slot.v = slot.v.at[slot.tok_pages, slot.tok_in_pages].set(
            v.value[0].astype(kd))
        plan = (None if slot.blk_pages is None else
                (slot.blk_pages, slot.blk_seq, slot.blk_start,
                 slot.blk_n))
        out = ragged_paged_attention(
            q.value[0], slot.k, slot.v, slot.page_table, slot.token_seq,
            slot.bounds, block_plan=plan)
        out = self.out_proj(Tensor(out.reshape(1, T, H).astype(
            x.value.dtype)))
        return out, slot

    def _forward_paged_cache(self, x, q, k, v, cache):
        """Continuous-batching path: write this step's k/v into the
        shared page pool, attend each row against its own paged history.
        Prefill (T>1) runs causal attention over the new tokens PLUS the
        paged history; decode (T==1) is one paged_attention gather."""
        from ..ops.paged_attention import paged_attention
        B, T, H = x.shape
        pc = cache.cache
        for i, sid in enumerate(cache.seq_ids):
            pc.extend(sid, cache.layer, k.value[i], v.value[i])
        # lengths are committed (advance) only after the LAST layer, so
        # batch_views here reports the pre-step history; the T tokens
        # this layer just wrote are added explicitly
        pt, old_lens = pc.batch_views(cache.seq_ids)
        if T == 1:
            out = paged_attention(q.value[:, 0], pc.k[cache.layer],
                                  pc.v[cache.layer], pt, old_lens + 1)
            out = out[:, None]
        else:
            # prefill: query position t sees history + new tokens <= t
            outs = [paged_attention(q.value[:, t], pc.k[cache.layer],
                                    pc.v[cache.layer], pt,
                                    old_lens + t + 1)
                    for t in range(T)]
            out = jnp.stack(outs, axis=1)
        if cache.layer == pc.n_layers - 1:
            for sid in cache.seq_ids:
                pc.advance(sid, T)
        out = self.out_proj(Tensor(out.reshape(B, T, H).astype(
            x.value.dtype)))
        return out, cache


class GPTMLP(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        w_init = nn.initializer.Normal(0.0, cfg.initializer_range)
        battr = None if cfg.use_bias else False
        self.fc_in = nn.Linear(cfg.hidden_size, cfg.intermediate_size,
                               weight_attr=w_init, bias_attr=battr)
        self.fc_out = nn.Linear(cfg.intermediate_size, cfg.hidden_size,
                                weight_attr=w_init, bias_attr=battr)
        self.drop = nn.Dropout(cfg.dropout)

    def forward(self, x):
        h = _ckpt_name(self.fc_in(x), "gpt_ffn_in")
        return self.drop(self.fc_out(F.gelu(h, approximate=True)))


class GPTBlock(nn.Layer):
    def __init__(self, cfg, use_moe=False):
        super().__init__()
        self.ln_1 = nn.LayerNorm(cfg.hidden_size,
                                 epsilon=cfg.layer_norm_epsilon)
        self.attn = GPTAttention(cfg)
        self.ln_2 = nn.LayerNorm(cfg.hidden_size,
                                 epsilon=cfg.layer_norm_epsilon)
        if use_moe:
            from ..incubate.moe import MoELayer
            self.mlp = MoELayer(cfg.hidden_size, cfg.intermediate_size,
                                num_experts=cfg.num_experts,
                                top_k=cfg.moe_top_k,
                                capacity_factor=cfg.moe_capacity_factor)
        else:
            self.mlp = GPTMLP(cfg)

    def forward(self, x, cache=None):
        if cache is not None:
            a, cache = self.attn(self.ln_1(x), cache)
            x = x + a
        else:
            x = x + self.attn(self.ln_1(x))
        x = x + self.mlp(self.ln_2(x))
        return (x, cache) if cache is not None else x


class GPTModel(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        w_init = nn.initializer.Normal(0.0, cfg.initializer_range)
        self.wte = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                weight_attr=w_init)
        self.wpe = nn.Embedding(cfg.max_position_embeddings,
                                cfg.hidden_size, weight_attr=w_init)
        self.drop = nn.Dropout(cfg.dropout)
        self.h = nn.LayerList([
            GPTBlock(cfg, use_moe=(cfg.num_experts > 0
                                   and i % cfg.moe_every
                                   == cfg.moe_every - 1))
            for i in range(cfg.num_layers)])
        self.ln_f = nn.LayerNorm(cfg.hidden_size,
                                 epsilon=cfg.layer_norm_epsilon)

    def forward(self, input_ids, position_ids=None, caches=None):
        B, T = input_ids.shape
        if position_ids is None:
            if caches is not None and isinstance(caches[0],
                                                 StaticCacheSlot):
                pos_arr = caches[0].pos + jnp.arange(T, dtype=jnp.int32)
                position_ids = Tensor(pos_arr[None, :])
            elif caches is not None and isinstance(caches[0],
                                                   PagedJitSlot):
                # pre-write length IS the new token's position
                position_ids = Tensor(
                    caches[0].lens[:, None].astype(jnp.int32))
            elif caches is not None and isinstance(caches[0],
                                                   PagedCacheSlot):
                pc = caches[0].cache
                lens = np.array([pc.length(s)
                                 for s in caches[0].seq_ids])[:, None]
                position_ids = Tensor(jnp.asarray(
                    lens + np.arange(T), jnp.int64))
            else:
                from ..tensor.creation import arange
                start = 0 if caches is None else caches[0][0].shape[1]
                position_ids = arange(start, start + T, dtype="int64"
                                      ).unsqueeze(0)
        x = self.drop(self.wte(input_ids) + self.wpe(position_ids))
        if caches is None and self._use_scan(x):
            x = self._scan_blocks(x)
            return self.ln_f(x)
        new_caches = []
        remat_fn = self._unrolled_remat(x) if caches is None else None
        for i, block in enumerate(self.h):
            if caches is not None:
                x, c = block(x, caches[i])
                new_caches.append(c)
            elif remat_fn is not None:
                x = remat_fn(block, x)
            else:
                x = block(x)
        x = self.ln_f(x)
        return (x, new_caches) if caches is not None else x

    def _unrolled_remat(self, x):
        """Per-block jax.checkpoint for the unrolled (scan_layers=False)
        path, honoring cfg.scan_remat exactly like _scan_blocks — without
        it, unrolled deep models lose memory control entirely. Only under
        trace: the eager tape manages its own storage."""
        import jax
        if not self.cfg.scan_remat or not isinstance(x.value,
                                                     jax.core.Tracer):
            return None
        policy = _remat_policy(self.cfg.scan_remat)

        def call(block, h):
            if not isinstance(block.mlp, GPTMLP):
                # MoE block: MoELayer records its aux loss on the layer
                # as a side channel; under jax.checkpoint that tracer
                # would leak out of the inner trace — run it unwrapped
                # (same reason _use_scan excludes MoE stacks)
                return block(h)
            fn = jax.checkpoint(lambda hv: block(Tensor(hv)).value,
                                prevent_cse=False, policy=policy)
            return Tensor(fn(h.value))

        return call

    def _use_scan(self, x):
        """Scan only under trace (the eager tape can't see through a raw
        lax.scan) and only when blocks draw no per-layer RNG (dropout
        layers are inert in eval mode, so eval always qualifies)."""
        import jax
        return (self.cfg.scan_layers and self.cfg.num_layers > 1
                and self.cfg.num_experts == 0  # MoE blocks: not uniform
                and (self.cfg.dropout == 0.0 or not self.training)
                and isinstance(x.value, jax.core.Tracer))

    def _scan_blocks(self, x):
        # Params are stacked here, inside the trace, rather than stored
        # stacked at rest: that keeps state_dict/named_parameters layout
        # per-layer (paddle semantics) at the cost of one XLA gather of
        # block weights per step — ~1% of step time at bench scale.
        import jax
        from ..jit.api import _bind, _restore
        blocks = list(self.h)
        proto = blocks[0]
        dicts = [dict(b.named_parameters()) for b in blocks]
        stacked = {k: jnp.stack([d[k].value for d in dicts])
                   for k in dicts[0]}

        def step(h, layer_params):
            saved = _bind(proto, layer_params)
            try:
                return proto(Tensor(h)).value
            finally:
                _restore(saved)

        if self.cfg.scan_remat:
            # scan_remat=True: full recompute (lowest memory, +2N flops
            # per token). scan_remat="dots": selective — save matmul/
            # attention outputs, recompute only cheap elementwise ops
            # (near-full-checkpoint memory savings without paying the
            # recompute FLOPs of the matmuls). The scan's while-loop
            # already blocks unsound CSE.
            step = jax.checkpoint(step, prevent_cse=False,
                                  policy=_remat_policy(self.cfg.scan_remat))
        y, _ = jax.lax.scan(lambda h, p: (step(h, p), None), x.value,
                            stacked)
        return Tensor(y)


class GPTForCausalLM(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.gpt = GPTModel(cfg)
        self.cfg = cfg

    def forward(self, input_ids, position_ids=None, caches=None):
        out = self.gpt(input_ids, position_ids, caches)
        hidden = out[0] if isinstance(out, tuple) else out
        # weight-tied LM head: logits = h @ wte^T (one big MXU matmul)
        from ..tensor.linalg import matmul
        logits = matmul(hidden, self.gpt.wte.weight, transpose_y=True)
        if isinstance(out, tuple):
            return logits, out[1]
        return logits

    def loss(self, input_ids, labels):
        logits = self(input_ids)
        V = logits.shape[-1]
        return F.cross_entropy(logits.reshape([-1, V]),
                               labels.reshape([-1]), ignore_index=-100)

    def fused_loss(self, input_ids, labels, chunk=2048):
        """LM loss WITHOUT materializing [B*T, V] logits: the weight-tied
        vocab projection and the softmax-xent run chunked under remat
        (ops/chunked_xent.py). The memory this frees is what lets 1.3B+
        single-chip configs raise their batch; numerics match .loss()
        to bf16 precision."""
        out = self.gpt(input_ids)
        hidden = out[0] if isinstance(out, tuple) else out
        from ..ops.chunked_xent import chunked_softmax_xent
        from ..framework.core import apply_op
        H = hidden.shape[-1]

        def fn(h, w, y):
            return chunked_softmax_xent(
                h.reshape(-1, H), w, y.reshape(-1), chunk=chunk)
        return apply_op(fn, hidden, self.gpt.wte.weight, labels)

    def make_paged_cache(self, n_pages, page_size=16, dtype=None):
        """Shared page pool sized for this model (continuous batching)."""
        from ..ops.paged_attention import PagedKVCache
        cfg = self.cfg
        return PagedKVCache(
            cfg.num_layers, n_pages, page_size, cfg.num_heads,
            cfg.hidden_size // cfg.num_heads,
            dtype or self.gpt.wte.weight.value.dtype)

    def paged_decode_step(self, cache, seq_ids, input_ids, pad_to=None):
        """One continuous-batching step over a shared PagedKVCache:
        prefill when input_ids has T>1 (new request joining the batch),
        decode when T==1. Rows are independent sequences; lengths may be
        ragged — each attends only its own paged history. Returns
        next-token logits [B, vocab].

        Decode runs as ONE jitted program (page pools donated, k/v rows
        scatter-written in batch) — the host only plans page ids; the
        per-layer host loop remains for prefill, where T varies.

        pad_to (decode only): pad the traced batch to a fixed size with
        rows targeting the reserved pad page (PagedKVCache.plan_decode),
        so a serving scheduler's decode program keeps ONE compiled shape
        while sequences join/leave; returned logits are sliced back to
        the real B."""
        B, T = input_ids.shape
        # poisoned-cache guard hoisted here so BOTH paths (T>1 prefill and
        # T==1 decode) fail with the explicit message instead of an opaque
        # NoneType error from the prefill slot plumbing
        if cache.k is None:
            raise RuntimeError(
                "this PagedKVCache was poisoned by an earlier failed "
                "step — rebuild it with make_paged_cache() and "
                "re-prefill in-flight sequences")
        # context-limit guard (both paths): inside jit the wpe gather
        # silently clamps out-of-range positions to the last row
        # (generate() raises for the same condition)
        limit = self.cfg.max_position_embeddings
        over = [s for s in seq_ids if cache.length(s) + T > limit]
        if over:
            raise ValueError(
                f"sequences {over!r} would exceed "
                f"max_position_embeddings={limit} after {T} token(s); "
                "free them or raise the limit")
        if T == 1:
            return self._paged_decode_jit(cache, seq_ids, input_ids,
                                          pad_to=pad_to)
        # the cache lock serializes allocator + pool mutations when a
        # second engine shares this pool (no-op cost when uncontended)
        with cache.lock:
            caches = [PagedCacheSlot(cache, l, list(seq_ids), None)
                      for l in range(self.cfg.num_layers)]
            logits, _ = self(input_ids, caches=caches)
            return logits[:, -1, :]

    def clear_decode_cache(self):
        """Refresh the decode param snapshot. Call after loading or
        mutating weights mid-serving (paged_decode_step reuses a frozen
        snapshot across steps). Compiled programs are kept — weights are
        traced arguments, so the executables stay valid."""
        self._paged_params = None

    def _paged_decode_jit(self, cache, seq_ids, input_ids, pad_to=None):
        import jax
        from ..jit.api import functional_call, state_arrays

        L = self.cfg.num_layers
        B = len(seq_ids)
        # params are frozen during serving: snapshot once (see
        # clear_decode_cache for mid-serving weight swaps)
        params = getattr(self, "_paged_params", None)
        if params is None:
            params = self._paged_params = state_arrays(self)[0]
        fn = getattr(self, "_paged_jit_fn", None)
        if fn is None:
            model = self

            def step(ps, kps, vps, toks, pages, in_pages, pt, lens):
                # Python side effects run at TRACE time only: this is
                # an exact count of decode executables compiled (one
                # per novel (B, table width) signature) — the serving
                # engine folds its delta into serve.retraces
                model._paged_decode_traces = getattr(
                    model, "_paged_decode_traces", 0) + 1
                slots = [PagedJitSlot(kps[l], vps[l], pages, in_pages,
                                      pt, lens) for l in range(L)]
                logits, out_slots = functional_call(
                    model, ps, {}, (Tensor(toks),),
                    kwargs={"caches": slots}, training=False)
                return (logits[:, -1, :], [s.k for s in out_slots],
                        [s.v for s in out_slots])

            # pools donated: page writes update HBM in place; jax.jit's
            # own cache keys on (B, table width) shapes
            fn = self._paged_jit_fn = jax.jit(step, donate_argnums=(1, 2))
        # the cache lock holds from the plan through the donated-pool
        # swap: a second engine sharing this pool (prefill/decode
        # disaggregation) must neither plan against pools this step is
        # about to donate nor interleave allocator mutations mid-plan
        with cache.lock:
            pages, in_pages, pt, lens = cache.plan_decode(seq_ids,
                                                          pad_to=pad_to)
            toks = input_ids.value.astype(jnp.int32)
            if pad_to is not None and pad_to > B:
                # pad rows decode token 0 at position 0 into the
                # reserved pad page — garbage by construction, sliced
                # off below
                toks = jnp.concatenate(
                    [toks, jnp.zeros((int(pad_to) - B, 1), jnp.int32)])
            try:
                logits, new_k, new_v = fn(
                    params, list(cache.k), list(cache.v), toks, pages,
                    in_pages, pt, lens)
            except Exception as e:
                # donation only consumes the pools once the compiled
                # program EXECUTES; a trace/compile failure leaves them
                # valid
                if not any(getattr(a, "is_deleted", lambda: False)()
                           for a in (*cache.k, *cache.v)):
                    raise
                # the pools were donated to the failed program — they
                # are gone; make the poisoned state loud instead of
                # letting the next step die with a bare "Array has been
                # deleted"
                cache.k = cache.v = None
                raise RuntimeError(
                    "jitted paged decode step failed AFTER its page "
                    "pools were donated — this PagedKVCache is "
                    "unrecoverable; rebuild it with make_paged_cache() "
                    "and re-prefill in-flight sequences") from e
            cache.k = list(new_k)
            cache.v = list(new_v)
            for sid in seq_ids:
                cache.advance(sid, 1)
        return Tensor(logits[:B])

    # ---- ragged mixed prefill+decode step ---------------------------
    RAGGED_TAG = "serve.ragged_step"

    def _ragged_jitted(self):
        """The one jax.jit wrapper every ragged signature lowers
        through (pools donated: page writes update HBM in place)."""
        fn = getattr(self, "_ragged_jit_fn", None)
        if fn is not None:
            return fn
        import jax
        from ..jit.api import functional_call

        model = self
        L = self.cfg.num_layers

        def step(ps, kps, vps, toks, pos, tok_seq, tok_pages,
                 tok_in_pages, bounds, pt, out_idx, temps, top_ks,
                 top_ps, rng_keys, blk_pages, blk_seq, blk_start,
                 blk_n):
            # trace-time side effect: exact count of ragged executables
            # traced (one per novel (T, B, W) signature) — the serving
            # engine folds the delta into serve.retraces
            model._ragged_traces = getattr(
                model, "_ragged_traces", 0) + 1
            slots = [RaggedJitSlot(kps[l], vps[l], tok_pages,
                                   tok_in_pages, pt, tok_seq, bounds,
                                   blk_pages, blk_seq, blk_start, blk_n)
                     for l in range(L)]
            logits, out_slots = functional_call(
                model, ps, {}, (Tensor(toks[None, :]),),
                kwargs={"caches": slots,
                        "position_ids": Tensor(pos[None, :])},
                training=False)
            last = logits[0][out_idx]          # [B, vocab]
            # sampling ON DEVICE, PER TOKEN: every slot t samples from
            # its own next-token logits under its OWNING ROW's config,
            # keyed fold_in(row_key, position[t]) — exactly the draw
            # the engine would make after consuming token t, which is
            # what lets a speculative verify row read the target's
            # sample at all k+1 positions from one step
            # (inference/speculative.py). Every op in sample_token_rows
            # is row-independent, so the out_idx gather reproduces the
            # old per-row result bit-exactly; the host still reads back
            # int32s, never vocab-sized logits
            nxt_tok = sample_token_rows(
                logits[0], temps[tok_seq], top_ks[tok_seq],
                top_ps[tok_seq], rng_keys[tok_seq], pos)
            nxt = nxt_tok[out_idx]
            return (last, nxt, nxt_tok, [s.k for s in out_slots],
                    [s.v for s in out_slots])

        fn = self._ragged_jit_fn = jax.jit(step, donate_argnums=(1, 2))
        return fn

    def ragged_arg_specs(self, cache, n_tokens, n_rows, width):
        """ShapeDtypeStructs of one ragged-step signature — what
        `warm_ragged` AOT-compiles ahead of traffic."""
        import jax
        from ..jit.api import state_arrays
        params = getattr(self, "_paged_params", None)
        if params is None:
            params = self._paged_params = state_arrays(self)[0]
        sds = jax.ShapeDtypeStruct
        pshape = (cache.n_pages, cache.page_size, cache.n_heads,
                  cache.head_dim)
        pools = [sds(pshape, cache.k[0].dtype)
                 for _ in range(self.cfg.num_layers)]
        i32 = jnp.int32
        B = int(n_rows)
        tok = lambda: sds((int(n_tokens),), i32)
        # the q-block plan's shapes derive from (T, B, W) through the
        # same choose_ragged_q_block the planner applies — still one
        # executable per (T, B, W) signature
        qb, s_cap = self._ragged_block_geometry(
            cache, n_tokens, n_rows, width)
        return (jax.tree.map(lambda a: sds(a.shape, a.dtype), params),
                pools, list(pools), tok(), tok(), tok(), tok(), tok(),
                tok(), sds((B, int(width)), i32), sds((B,), i32),
                # per-row sampling config: [B]-shaped like out_idx, so
                # the signature still keys on (T, B, W) only
                sds((B,), jnp.float32), sds((B,), i32),
                sds((B,), jnp.float32), sds((B, 2), jnp.uint32),
                sds((qb, s_cap), i32), sds((qb, s_cap), i32),
                sds((qb, s_cap), i32), sds((qb,), i32))

    def _ragged_block_geometry(self, cache, n_tokens, n_rows, width):
        """(QB, S) of the q-block plan arrays for one (T, B, W)
        signature — the shape contract between plan_ragged's host
        planner and the compiled step."""
        from ..ops.pallas.attention_core import choose_ragged_q_block
        fold = max(self.cfg.num_heads // cache.n_heads, 1)
        q_block = choose_ragged_q_block(int(n_tokens), fold)
        return int(n_tokens) // q_block, int(n_rows) * int(width)

    _RAGGED_ARG_NAMES = ("params", "k_pages", "v_pages", "tokens",
                         "positions", "token_seq", "tok_pages",
                         "tok_in_pages", "bounds", "page_table",
                         "out_idx", "temperatures", "top_ks", "top_ps",
                         "rng_keys", "blk_pages", "blk_seq",
                         "blk_start", "blk_n")

    @staticmethod
    def _ragged_sig(cache, n_tokens, n_rows, width):
        return (int(n_tokens), int(n_rows), int(width),
                int(cache.n_pages), int(cache.page_size),
                str(cache.k[0].dtype) if cache.k else "poisoned")

    def warm_ragged(self, cache, n_tokens, n_rows, width, inline=False):
        """Single-flight AOT compile of one ragged signature through
        the background warm pipeline (jit/warm.py). Returns the
        WarmHandle; `handle.result()` is the (compiled, info) entry. A
        dispatch racing this JOINS the in-flight compile."""
        from ..jit import warm as _warm
        from ..jit.api import aot_compile
        exec_cache = getattr(self, "_ragged_exec", None)
        if exec_cache is None:
            exec_cache = self._ragged_exec = {}
        # the pool geometry is part of the executable's signature: two
        # engines over one model with different page pools must not
        # collide on compiled programs
        sig = self._ragged_sig(cache, n_tokens, n_rows, width)
        specs = self.ragged_arg_specs(cache, n_tokens, n_rows, width)
        jitted = self._ragged_jitted()

        def thunk():
            return aot_compile(jitted, specs, tag=self.RAGGED_TAG,
                               arg_names=self._RAGGED_ARG_NAMES)

        return _warm.submit_cached(exec_cache, sig, self.RAGGED_TAG,
                                   thunk, inline=inline)

    def paged_ragged_step(self, cache, rows, pad_to_tokens=None,
                          pad_to_rows=None, sampling=None,
                          return_per_token=False):
        """ONE continuous-batching step over mixed rows: `rows` is a
        list of (seq_id, token_ids) where decode rows carry one token
        and prefill-chunk rows carry a slice of their prompt — all
        advanced in a single jitted program over the Pallas ragged
        kernel, each token attending only its own paged history (pad
        tokens do zero attention work).

        Returns (logits Tensor [n_rows, vocab] — each row's LAST
        token's next-token logits — and next_tokens, a device int32
        array sampled ON DEVICE per row: no vocab-sized host read).
        pad_to_tokens/pad_to_rows pin the compiled shape for a serving
        scheduler.

        `sampling` is an optional (temperatures, top_ks, top_ps,
        rng_keys) tuple of PADDED-row-shaped host arrays (f32 [B],
        i32 [B], f32 [B], u32 [B, 2] — see `sample_token_rows`); None
        means every row decodes greedily (temperature 0), bit-exact
        with the pre-sampling argmax path.

        `return_per_token=True` appends the full padded [T] int32
        device array of PER-TOKEN samples (slot t's draw from its own
        next-token logits under its owning row's config, keyed by slot
        t's absolute position) — what a speculative verify row reads to
        compare the target's sample at every draft position
        (inference/speculative.py). The same one executable serves both
        callers; the flag only changes what the host unpacks."""
        if cache.k is None:
            raise RuntimeError(
                "this PagedKVCache was poisoned by an earlier failed "
                "step — rebuild it with make_paged_cache() and "
                "re-prefill in-flight sequences")
        limit = self.cfg.max_position_embeddings
        over = [s for s, t in rows
                if cache.length(s) + len(t) > limit]
        if over:
            raise ValueError(
                f"sequences {over!r} would exceed "
                f"max_position_embeddings={limit}; free them or raise "
                "the limit")
        from ..jit.api import state_arrays
        params = getattr(self, "_paged_params", None)
        if params is None:
            params = self._paged_params = state_arrays(self)[0]
        # the cache lock holds from the plan through the donated-pool
        # swap (see _paged_decode_jit): with two engines sharing one
        # pool, the other engine's step must see either the pre- or
        # the post-step pool buffers, never the donated carcass
        with cache.lock:
            plan = cache.plan_ragged([(s, len(t)) for s, t in rows],
                                     pad_to_tokens=pad_to_tokens,
                                     pad_to_rows=pad_to_rows,
                                     q_heads=self.cfg.num_heads)
            T = plan["tok_pages"].shape[0]
            B, W = plan["page_table"].shape
            toks = np.zeros((T,), np.int32)
            off = 0
            for _, t in rows:
                toks[off:off + len(t)] = \
                    np.asarray(t, np.int32).reshape(-1)
                off += len(t)
            entry = getattr(self, "_ragged_exec", {}).get(
                self._ragged_sig(cache, T, B, W))
            if entry is None:
                # miss: compile inline (single-flight — a concurrent
                # warm of the same signature is joined, not duplicated)
                entry = self.warm_ragged(cache, T, B, W,
                                         inline=True).result()
            compiled, _ = entry
            if sampling is None:
                # greedy defaults: temp-0 rows take the argmax lane
                sampling = (np.zeros((B,), np.float32),
                            np.zeros((B,), np.int32),
                            np.ones((B,), np.float32),
                            np.zeros((B, 2), np.uint32))
            temps, top_ks, top_ps, rng_keys = sampling
            args = (params, list(cache.k), list(cache.v),
                    jnp.asarray(toks), jnp.asarray(plan["positions"]),
                    jnp.asarray(plan["token_seq"]),
                    jnp.asarray(plan["tok_pages"]),
                    jnp.asarray(plan["tok_in_pages"]),
                    jnp.asarray(plan["bounds"]),
                    jnp.asarray(plan["page_table"]),
                    jnp.asarray(plan["out_idx"]),
                    jnp.asarray(temps), jnp.asarray(top_ks),
                    jnp.asarray(top_ps), jnp.asarray(rng_keys),
                    jnp.asarray(plan["blk_pages"]),
                    jnp.asarray(plan["blk_seq"]),
                    jnp.asarray(plan["blk_start"]),
                    jnp.asarray(plan["blk_n"]))
            try:
                last, nxt, nxt_tok, new_k, new_v = compiled(*args)
            except Exception as e:
                # donation only consumes the pools once the program
                # EXECUTES; a dispatch failure before that leaves them
                # valid
                if not any(getattr(a, "is_deleted", lambda: False)()
                           for a in (*cache.k, *cache.v)):
                    raise
                cache.k = cache.v = None
                raise RuntimeError(
                    "jitted ragged step failed AFTER its page pools "
                    "were donated — this PagedKVCache is "
                    "unrecoverable; rebuild it with make_paged_cache() "
                    "and re-prefill in-flight sequences") from e
            cache.k = list(new_k)
            cache.v = list(new_v)
            for s, t in rows:
                cache.advance(s, len(t))
            n = plan["n_rows"]
        if return_per_token:
            return Tensor(last[:n]), nxt[:n], nxt_tok
        return Tensor(last[:n]), nxt[:n]

    def generate(self, input_ids, max_new_tokens=32, temperature=1.0,
                 top_k=None, top_p=None):
        """Top-k/temperature sampling over a STATIC KV cache.

        Exactly two compiled programs regardless of max_new_tokens: one
        prefill over the prompt (fills the [B, L, H, D] buffers in a
        single pass) and one lax.scan over the decode steps (each step
        writes its k/v at the current position and attends under a
        `col <= pos` mask). Replaces the per-token concat path that
        recompiled every step (ref generate() in PaddleNLP GPT; decode
        design per VERDICT r2 weak #5)."""
        import jax
        from ..jit.api import functional_call, state_arrays
        from ..framework.random import split_key

        cfg = self.cfg
        B, T = input_ids.shape
        L = T + max_new_tokens
        if L > cfg.max_position_embeddings:
            raise ValueError(
                f"prompt {T} + max_new_tokens {max_new_tokens} exceeds "
                f"max_position_embeddings {cfg.max_position_embeddings}")
        nh, hd = cfg.num_heads, cfg.hidden_size // cfg.num_heads
        params, _ = state_arrays(self)
        cache_dtype = self.gpt.wte.weight.value.dtype
        model = self

        def fwd(ps, ids, kbs, vbs, pos):
            caches = [StaticCacheSlot(Tensor(kbs[i]), Tensor(vbs[i]), pos)
                      for i in range(cfg.num_layers)]
            logits, new_caches = functional_call(
                model, ps, {}, (Tensor(ids),), kwargs={"caches": caches},
                training=False)
            kbs = jnp.stack([c.k.value for c in new_caches])
            vbs = jnp.stack([c.v.value for c in new_caches])
            return logits, kbs, vbs

        def sample(last, key, temp):
            arr = last.astype(jnp.float32) / jnp.maximum(temp, 1e-6)
            V = arr.shape[-1]
            # approx path: lax.approx_max_k thresholds 29x faster than
            # exact top_k over a 50k vocab (0.05 ms vs 1.6 ms at batch
            # 32) and is accurate to the nucleus/kth boundary. Default on
            # TPU for big vocabs; PADDLE_TPU_APPROX_SAMPLING=0/1 forces
            # it off/on (on works on every backend — tests compare the
            # two paths on CPU).
            force = os.environ.get("PADDLE_TPU_APPROX_SAMPLING")
            approx = (jax.default_backend() == "tpu" and V > 8192) \
                if force is None else force == "1"
            # one descending approx-top scan, sized to what's needed:
            # top-k alone only needs the kth value, the nucleus needs a
            # few thousand entries to cover top_p
            n_sub = min(V, 4096 if top_p is not None else (top_k or 0))
            subset = None
            if approx and n_sub > 0:
                subset, _ = jax.lax.approx_max_k(arr, n_sub,
                                                 recall_target=0.99)

            def nucleus_thresh(srt, p_srt):
                # keep the smallest prefix of the sorted probs reaching
                # top_p (a token stays iff the mass BEFORE it is < top_p)
                before = jnp.cumsum(p_srt, axis=-1) - p_srt
                keep = before < top_p
                return jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1,
                               keepdims=True)

            if top_k is not None:
                if subset is not None and top_k <= n_sub:
                    kth = subset[:, top_k - 1:top_k]
                else:
                    kth = jax.lax.top_k(arr, top_k)[0][:, -1:]
                arr = jnp.where(arr < kth, -1e30, arr)
            if top_p is not None:
                if subset is not None:
                    # sort only the approx-top subset, normalized against
                    # the full-row softmax mass; if the subset doesn't
                    # cover top_p (near-uniform logits), keep everything
                    # rather than truncate at the subset edge
                    lse = jax.scipy.special.logsumexp(arr, axis=-1,
                                                      keepdims=True)
                    p_sub = jnp.exp(subset - lse)
                    thresh = nucleus_thresh(subset, p_sub)
                    covered = jnp.sum(p_sub, axis=-1,
                                      keepdims=True) >= top_p
                    thresh = jnp.where(covered, thresh, -jnp.inf)
                else:
                    srt = jnp.sort(arr, axis=-1)[:, ::-1]
                    thresh = nucleus_thresh(srt,
                                            jax.nn.softmax(srt, axis=-1))
                arr = jnp.where(arr >= thresh, arr, -1e30)
            return jax.random.categorical(key, arr)[:, None]

        def prefill(ps, ids, key, temp):
            kbs = jnp.zeros((cfg.num_layers, B, L, nh, hd), cache_dtype)
            vbs = jnp.zeros_like(kbs)
            logits, kbs, vbs = fwd(ps, ids, kbs, vbs, 0)
            return sample(logits[:, -1, :], key, temp), kbs, vbs

        def decode(ps, first_tok, kbs, vbs, key, temp):
            def step(carry, i):
                tok, kbs, vbs = carry
                logits, kbs, vbs = fwd(ps, tok, kbs, vbs, T + i)
                nxt = sample(logits[:, -1, :],
                             jax.random.fold_in(key, i), temp)
                return (nxt, kbs, vbs), nxt[:, 0]

            _, toks = jax.lax.scan(step, (first_tok, kbs, vbs),
                                   jnp.arange(max_new_tokens - 1))
            return jnp.concatenate([first_tok, toks.T], axis=1)

        sig = (B, T, max_new_tokens, top_k, top_p)
        cache = getattr(self, "_gen_jit", None)
        if cache is None:
            cache = self._gen_jit = {}
        if sig not in cache:
            cache[sig] = (jax.jit(prefill),
                          jax.jit(decode) if max_new_tokens > 1 else None)
        jit_prefill, jit_decode = cache[sig]

        ids = input_ids.value.astype(jnp.int32)
        temp = jnp.asarray(temperature, jnp.float32)
        first_tok, kbs, vbs = jit_prefill(params, ids, split_key(), temp)
        if jit_decode is None:
            new = first_tok
        else:
            new = jit_decode(params, first_tok, kbs, vbs, split_key(),
                             temp)
        out = jnp.concatenate([input_ids.value.astype(jnp.int64),
                               new.astype(jnp.int64)], axis=1)
        return Tensor(out)


def gpt_tiny(vocab=1024):
    return GPTConfig(vocab_size=vocab, hidden_size=64, num_layers=2,
                     num_heads=4, max_position_embeddings=128)


def gpt_small():
    return GPTConfig(hidden_size=768, num_layers=12, num_heads=12)


def gpt_medium():
    # scan_remat="names": at this width's training shape (batch 8 x seq
    # 1024, bf16 + f32 masters) the scanned stack with nothing
    # rematerialised needs 19.5 GB of a v5e's 15.75 GB; saving the
    # three big matmul outputs per block and recomputing the rest
    # compiles at 14.1 GB (the chip's compiler, PR 21)
    return GPTConfig(hidden_size=1024, num_layers=24, num_heads=16,
                     scan_remat="names")


def gpt_1p3b():
    return GPTConfig(hidden_size=2048, num_layers=24, num_heads=16,
                     max_position_embeddings=2048)


def gpt_6p7b():
    return GPTConfig(hidden_size=4096, num_layers=32, num_heads=32,
                     max_position_embeddings=2048)


def gpt_moe(num_experts=8, **kw):
    """MoE flagship: GPT-small trunk with every 2nd MLP an
    expert-parallel MoELayer (experts shard over 'ep')."""
    kw.setdefault("hidden_size", 768)
    kw.setdefault("num_layers", 12)
    kw.setdefault("num_heads", 12)
    return GPTConfig(num_experts=num_experts, **kw)
