"""Mamba-style selective-state-space models with the GPT serving contract.

The second model family behind the serving stack (PAPERS.md
"Compiler-First State Space Duality and Portable O(1) Autoregressive
Caching"): a stack of selective-SSM mixer blocks — optionally
interleaved with attention layers (`attn_every`) — whose decode cache
is ONE fixed-size state blob per sequence (conv tail + state matrix
per layer) instead of a length-proportional KV page list. The
continuous-batching engine, router, and disaggregation all drive it
through the same duck-typed surface `models/gpt.py` defined:

    make_paged_cache()    -> inference.cache_strategy.RecurrentStateCache
                             (or HybridCache for the interleaved model)
    paged_ragged_step()   the fixed-shape mixed prefill+decode step —
                          same `serve.ragged_step` warm/executable
                          ledger discipline, same on-device per-row
                          sampling (gpt.sample_token_rows)
    warm_ragged()         single-flight AOT compiles, shared tag
    paged_decode_step()   eager wrapper over the ragged step (the
                          tests' single-sequence reference oracle)

The selective scan itself is the Pallas kernel in
ops/pallas/ssm_scan.py; the FULL forward (training path) flattens
[B, T] onto the kernel's ragged token axis, so training and serving
execute the identical scan code. Chunked prefill needs no special
path: a prompt slice is just a multi-token row of the ragged step,
its conv tail and state carrying across chunks through the pools.
"""
import numpy as np
import jax
import jax.numpy as jnp

from ..framework.core import Tensor
from .. import nn
from ..nn import initializer as I
from .gpt import GPTAttention, RaggedJitSlot, sample_token_rows

__all__ = ["SSMConfig", "SSMForCausalLM", "SSMJitSlot", "ssm_tiny",
           "ssm_hybrid_tiny"]


class SSMConfig:
    def __init__(self, vocab_size=50304, hidden_size=768, num_layers=12,
                 d_state=16, d_conv=4, expand=2, dt_rank=None,
                 attn_every=0, num_heads=12,
                 max_position_embeddings=1024, dropout=0.0,
                 layer_norm_epsilon=1e-5, initializer_range=0.02,
                 use_bias=True, sequence_parallel=False):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.d_state = d_state          # N: state matrix columns
        self.d_conv = d_conv            # K: causal depthwise conv taps
        self.expand = expand
        self.d_inner = expand * hidden_size
        self.dt_rank = dt_rank or max(hidden_size // 16, 1)
        # attn_every=k > 0: every k-th layer is a GPTAttention layer
        # (the hybrid model); 0 = pure SSM stack
        self.attn_every = attn_every
        self.num_heads = num_heads
        # SSM state has no positional ceiling; the limit stays as the
        # engine's context-guard contract (and bounds the hybrid wpe)
        self.max_position_embeddings = max_position_embeddings
        self.dropout = dropout
        self.layer_norm_epsilon = layer_norm_epsilon
        self.initializer_range = initializer_range
        self.use_bias = use_bias
        self.sequence_parallel = sequence_parallel

    def is_attn_layer(self, i):
        return self.attn_every > 0 \
            and i % self.attn_every == self.attn_every - 1


class SSMJitSlot:
    """One SSM layer's state for the fully-jitted RAGGED step:
    traced/donated conv + state pools plus the host plan from
    RecurrentStateCache.plan_step — per-token row/chunk coordinates,
    the dt validity mask that neutralizes pad tokens, and the per-row
    slot/boundary arrays the conv-tail update needs."""

    __slots__ = ("conv", "ssm", "token_seq", "chunk_pos", "tok_valid",
                 "slot_ids", "row_end", "row_len")

    def __init__(self, conv, ssm, token_seq, chunk_pos, tok_valid,
                 slot_ids, row_end, row_len):
        self.conv = conv
        self.ssm = ssm
        self.token_seq = token_seq
        self.chunk_pos = chunk_pos
        self.tok_valid = tok_valid
        self.slot_ids = slot_ids
        self.row_end = row_end
        self.row_len = row_len


class SSMMixer(nn.Layer):
    """Selective-SSM token mixer (Mamba block body): in-projection to
    (x, z), causal depthwise conv over x, input-dependent (dt, B, C)
    from x, the selective scan h_t = exp(dt*A)h_{t-1} + (dt*B_t)x_t /
    y_t = C_t.h_t + D*x_t, silu(z) gating, out-projection. The scan is
    ops/pallas/ssm_scan.py in BOTH the full forward and the ragged
    serving step."""

    def __init__(self, cfg):
        super().__init__()
        h, d = cfg.hidden_size, cfg.d_inner
        N, K, R = cfg.d_state, cfg.d_conv, cfg.dt_rank
        self.d_inner, self.d_state, self.d_conv = d, N, K
        w_init = nn.initializer.Normal(0.0, cfg.initializer_range)
        self.in_proj = nn.Linear(h, 2 * d, weight_attr=w_init,
                                 bias_attr=False)
        self.conv_weight = self.create_parameter(
            [K, d], default_initializer=w_init)
        self.conv_bias = self.create_parameter([d], is_bias=True)
        self.x_proj = nn.Linear(d, R + 2 * N, weight_attr=w_init,
                                bias_attr=False)
        self.dt_proj = nn.Linear(R, d, weight_attr=w_init)
        # S4/Mamba A init: A = -exp(A_log) with A_log = log(1..N) per
        # channel — a spread of decay rates; D (skip) starts at 1
        self.A_log = self.create_parameter(
            [d, N], default_initializer=I.Assign(
                np.log(np.tile(np.arange(1, N + 1, dtype=np.float32),
                               (d, 1)))))
        self.D = self.create_parameter(
            [d], default_initializer=I.Constant(1.0))
        self.out_proj = nn.Linear(d, h, weight_attr=w_init,
                                  bias_attr=None if cfg.use_bias
                                  else False)

    def _dt_bc(self, xc):
        """(dt [.., d], B [.., N], C [.., N]) from the conv output —
        the input-dependence that makes the scan selective. dt is
        softplus'd here; the caller masks pads."""
        R, N = self.x_proj.weight.shape[1] - 2 * self.d_state, \
            self.d_state
        dbc = self.x_proj(Tensor(xc)).value
        dt = jax.nn.softplus(self.dt_proj(Tensor(dbc[..., :R])).value)
        return dt, dbc[..., R:R + N], dbc[..., R + N:]

    def forward(self, x, slot=None):
        from ..ops.pallas.ssm_scan import ssm_scan
        B, T, H = x.shape
        d, N, K = self.d_inner, self.d_state, self.d_conv
        xz = self.in_proj(x).value
        xin, z = xz[..., :d], xz[..., d:]
        w = self.conv_weight.value.astype(jnp.float32)
        if slot is None:
            # full causal forward: conv via shifts from zeros, scan via
            # the kernel with [B, T] flattened onto the token axis (the
            # serving kernel IS the training kernel)
            acc = xin * w[K - 1]
            for s in range(1, K):
                prev = jnp.pad(xin, ((0, 0), (s, 0), (0, 0)))[:, :T]
                acc = acc + prev * w[K - 1 - s]
            xc = jax.nn.silu(acc + self.conv_bias.value)
            dt, b_t, c_t = self._dt_bc(xc)
            h0 = jnp.zeros((B, d, N), jnp.float32)
            token_seq = jnp.repeat(jnp.arange(B, dtype=jnp.int32), T)
            y, _ = ssm_scan(xc.reshape(B * T, d).astype(jnp.float32),
                            dt.reshape(B * T, d).astype(jnp.float32),
                            b_t.reshape(B * T, N).astype(jnp.float32),
                            c_t.reshape(B * T, N).astype(jnp.float32),
                            -jnp.exp(self.A_log.value), h0, token_seq)
            y = y.reshape(B, T, d) + xc * self.D.value
            y = y * jax.nn.silu(z)
            return self.out_proj(Tensor(y.astype(x.value.dtype)))
        # ragged serving step: B == 1, the token axis carries the batch
        xin, z = xin[0], z[0]
        tslot = slot.slot_ids[slot.token_seq]     # per-token pool slot
        acc = xin * w[K - 1]
        for s in range(1, K):
            # token s-back: this chunk when chunk_pos >= s, else the
            # row's saved conv tail (age s - chunk_pos at save time)
            prev_new = jnp.pad(xin, ((s, 0), (0, 0)))[:T]
            sidx = jnp.clip(slot.chunk_pos + (K - 1 - s), 0, K - 2)
            prev_old = slot.conv[tslot, sidx]
            prev = jnp.where((slot.chunk_pos >= s)[:, None], prev_new,
                             prev_old)
            acc = acc + prev * w[K - 1 - s]
        xc = jax.nn.silu(acc + self.conv_bias.value)
        dt, b_t, c_t = self._dt_bc(xc)
        # pads become identity state updates BY CONSTRUCTION (see
        # ssm_scan module doc): zero dt -> exp(0)h + 0
        dt = dt * slot.tok_valid[:, None]
        h0 = slot.ssm[slot.slot_ids].astype(jnp.float32)
        y, h_out = ssm_scan(xc.astype(jnp.float32),
                            dt.astype(jnp.float32),
                            b_t.astype(jnp.float32),
                            c_t.astype(jnp.float32),
                            -jnp.exp(self.A_log.value), h0,
                            slot.token_seq)
        slot.ssm = slot.ssm.at[slot.slot_ids].set(
            h_out.astype(slot.ssm.dtype))
        # conv-tail update: slot j holds the input aged K-1-j tokens
        # before the row's NEXT token — from this chunk's last tokens
        # when the row contributed enough, else the old tail shifted
        # by row_len (pad rows: row_len 0 rewrites slot 0 harmlessly)
        ages = jnp.arange(1, K, dtype=jnp.int32)
        idx = jnp.clip(slot.row_end[:, None] - ages[None, :], 0, T - 1)
        from_new = xin[idx]
        old = slot.conv[slot.slot_ids]
        shift = jnp.clip(K - 1 - ages[None, :] + slot.row_len[:, None],
                         0, K - 2)
        from_old = jnp.take_along_axis(old, shift[:, :, None], axis=1)
        keep_new = (ages[None, :] <= slot.row_len[:, None])[:, :, None]
        new_tail = jnp.where(keep_new, from_new, from_old)[:, ::-1]
        slot.conv = slot.conv.at[slot.slot_ids].set(
            new_tail.astype(slot.conv.dtype))
        y = y + xc * self.D.value
        y = y * jax.nn.silu(z)
        return self.out_proj(Tensor(y[None].astype(x.value.dtype))), \
            slot


class SSMBlock(nn.Layer):
    """Pre-norm residual block around one mixer — an SSMMixer, or a
    GPTAttention layer in the hybrid interleave. No separate MLP: the
    SSM mixer carries its own `expand`x inner width (Mamba's block
    shape), and hybrid attention layers ride the same skeleton."""

    def __init__(self, cfg, use_attn=False):
        super().__init__()
        self.ln_1 = nn.LayerNorm(cfg.hidden_size,
                                 epsilon=cfg.layer_norm_epsilon)
        self.mixer = GPTAttention(cfg) if use_attn else SSMMixer(cfg)

    def forward(self, x, cache=None):
        if cache is not None:
            a, cache = self.mixer(self.ln_1(x), cache)
            return x + a, cache
        return x + self.mixer(self.ln_1(x))


class SSMModel(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        w_init = nn.initializer.Normal(0.0, cfg.initializer_range)
        self.wte = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                weight_attr=w_init)
        self.hybrid = cfg.attn_every > 0
        if self.hybrid:
            # only attention needs absolute positions; the pure SSM
            # stack is position-aware through its recurrence alone
            self.wpe = nn.Embedding(cfg.max_position_embeddings,
                                    cfg.hidden_size, weight_attr=w_init)
        self.h = nn.LayerList([
            SSMBlock(cfg, use_attn=cfg.is_attn_layer(i))
            for i in range(cfg.num_layers)])
        self.ln_f = nn.LayerNorm(cfg.hidden_size,
                                 epsilon=cfg.layer_norm_epsilon)

    def forward(self, input_ids, position_ids=None, caches=None):
        B, T = input_ids.shape
        x = self.wte(input_ids)
        if self.hybrid:
            if position_ids is None:
                from ..tensor.creation import arange
                position_ids = arange(0, T, dtype="int64").unsqueeze(0)
            x = x + self.wpe(position_ids)
        if caches is None:
            for block in self.h:
                x = block(x)
            return self.ln_f(x)
        new_caches = []
        for i, block in enumerate(self.h):
            x, c = block(x, caches[i])
            new_caches.append(c)
        return self.ln_f(x), new_caches


class SSMForCausalLM(nn.Layer):
    """Causal LM head over the SSM trunk, exposing the SAME serving
    surface as gpt.GPTForCausalLM (see module doc) so
    GenerationEngine/ServingRouter drive it unchanged — only the cache
    strategy underneath differs."""

    def __init__(self, cfg):
        super().__init__()
        self.ssm = SSMModel(cfg)
        self.cfg = cfg

    def forward(self, input_ids, position_ids=None, caches=None):
        out = self.ssm(input_ids, position_ids, caches)
        hidden = out[0] if isinstance(out, tuple) else out
        from ..tensor.linalg import matmul
        logits = matmul(hidden, self.ssm.wte.weight, transpose_y=True)
        if isinstance(out, tuple):
            return logits, out[1]
        return logits

    def loss(self, input_ids, labels):
        from ..nn import functional as F
        logits = self(input_ids)
        V = logits.shape[-1]
        return F.cross_entropy(logits.reshape([-1, V]),
                               labels.reshape([-1]), ignore_index=-100)

    # ---- serving surface (the GPT duck type) -------------------------
    def make_paged_cache(self, n_pages, page_size=16, dtype=None):
        """The strategy-appropriate pool for this model: a
        RecurrentStateCache of n_pages - 1 state slots (the historical
        `n_pages` parameter keeps the engine's capacity arithmetic —
        slot 0 reserved, usable = n_pages - 1), or a HybridCache
        pairing it with a PagedKVCache over the attention layers."""
        from ..inference.cache_strategy import (RecurrentStateCache,
                                                HybridCache)
        cfg = self.cfg
        dtype = dtype or self.ssm.wte.weight.value.dtype
        n_ssm = sum(1 for i in range(cfg.num_layers)
                    if not cfg.is_attn_layer(i))
        rec = RecurrentStateCache(
            n_layers=n_ssm, n_slots=int(n_pages) - 1,
            d_inner=cfg.d_inner, d_state=cfg.d_state,
            d_conv=cfg.d_conv, dtype=dtype, page_size=page_size)
        if not self.ssm.hybrid:
            return rec
        from ..ops.paged_attention import PagedKVCache
        n_attn = cfg.num_layers - n_ssm
        paged = PagedKVCache(n_attn, n_pages, page_size, cfg.num_heads,
                             cfg.hidden_size // cfg.num_heads, dtype)
        return HybridCache(paged, rec)

    def clear_decode_cache(self):
        """Refresh the decode param snapshot after mutating weights
        mid-serving (compiled programs stay valid — params are traced
        arguments)."""
        self._paged_params = None

    def paged_decode_step(self, cache, seq_ids, input_ids, pad_to=None):
        """Eager continuous-batching step (prefill when T > 1, decode
        when T == 1) — a host wrapper over the ragged step, so the
        single-sequence reference oracle and the serving path run the
        SAME compiled program. Returns next-token logits [B, vocab]."""
        del pad_to  # the ragged step buckets its own shapes
        B, T = input_ids.shape
        self._check_pools(cache)
        toks = np.asarray(input_ids.value).astype(np.int32)
        rows = [(sid, toks[i].reshape(-1))
                for i, sid in enumerate(seq_ids)]
        last, _ = self.paged_ragged_step(cache, rows)
        return last

    # ---- ragged mixed prefill+decode step ----------------------------
    RAGGED_TAG = "serve.ragged_step"

    def _check_pools(self, cache):
        rec = getattr(cache, "recurrent", cache)
        dead = rec.conv is None or (self.ssm.hybrid
                                    and cache.paged.k is None)
        if dead:
            raise RuntimeError(
                "this cache was poisoned by an earlier failed step — "
                "rebuild it with make_paged_cache() and re-prefill "
                "in-flight sequences")

    def _poison(self, cache):
        rec = getattr(cache, "recurrent", cache)
        rec.conv = rec.ssm = None
        if self.ssm.hybrid:
            cache.paged.k = cache.paged.v = None

    def _donated_pools(self, cache):
        rec = getattr(cache, "recurrent", cache)
        pools = list(rec.conv) + list(rec.ssm)
        if self.ssm.hybrid:
            pools += list(cache.paged.k) + list(cache.paged.v)
        return pools

    def _ragged_jitted(self):
        """The one jax.jit wrapper every ragged signature lowers
        through (state pools — and, hybrid, kv page pools — donated:
        writes update HBM in place)."""
        fn = getattr(self, "_ragged_jit_fn", None)
        if fn is not None:
            return fn
        from ..jit.api import functional_call

        model = self
        cfg = self.cfg
        ssm_of = {}   # layer index -> index into the state pool lists
        attn_of = {}  # layer index -> index into the kv pool lists
        for i in range(cfg.num_layers):
            if cfg.is_attn_layer(i):
                attn_of[i] = len(attn_of)
            else:
                ssm_of[i] = len(ssm_of)

        def build_slots(kps, vps, convs, ssms, toks, pos, tok_seq,
                        chunk_pos, tok_valid, slot_ids, row_end,
                        row_len, attn_plan, out_idx, temps, top_ks,
                        top_ps, rng_keys):
            # trace-time side effect: exact count of ragged executables
            # traced — the serving engine folds the delta into
            # serve.retraces
            model._ragged_traces = getattr(
                model, "_ragged_traces", 0) + 1
            slots = []
            for i in range(cfg.num_layers):
                if i in attn_of:
                    a = attn_of[i]
                    (tok_pages, tok_in_pages, bounds, pt, blk_pages,
                     blk_seq, blk_start, blk_n) = attn_plan
                    slots.append(RaggedJitSlot(
                        kps[a], vps[a], tok_pages, tok_in_pages, pt,
                        tok_seq, bounds, blk_pages, blk_seq, blk_start,
                        blk_n))
                else:
                    j = ssm_of[i]
                    slots.append(SSMJitSlot(
                        convs[j], ssms[j], tok_seq, chunk_pos,
                        tok_valid, slot_ids, row_end, row_len))
            logits, out_slots = functional_call(
                model, build_slots.params, {}, (Tensor(toks[None, :]),),
                kwargs={"caches": slots,
                        "position_ids": Tensor(pos[None, :])},
                training=False)
            last = logits[0][out_idx]
            nxt_tok = sample_token_rows(
                logits[0], temps[tok_seq], top_ks[tok_seq],
                top_ps[tok_seq], rng_keys[tok_seq], pos)
            nxt = nxt_tok[out_idx]
            ssm_out = [s for s in out_slots if isinstance(s, SSMJitSlot)]
            attn_out = [s for s in out_slots
                        if isinstance(s, RaggedJitSlot)]
            return (last, nxt, nxt_tok, attn_out, ssm_out)

        if self.ssm.hybrid:
            def step(ps, kps, vps, convs, ssms, toks, pos, tok_seq,
                     chunk_pos, tok_valid, slot_ids, row_end, row_len,
                     tok_pages, tok_in_pages, bounds, pt, blk_pages,
                     blk_seq, blk_start, blk_n, out_idx, temps, top_ks,
                     top_ps, rng_keys):
                build_slots.params = ps
                last, nxt, nxt_tok, attn_out, ssm_out = build_slots(
                    kps, vps, convs, ssms, toks, pos, tok_seq,
                    chunk_pos, tok_valid, slot_ids, row_end, row_len,
                    (tok_pages, tok_in_pages, bounds, pt, blk_pages,
                     blk_seq, blk_start, blk_n), out_idx, temps,
                    top_ks, top_ps, rng_keys)
                return (last, nxt, nxt_tok,
                        [s.k for s in attn_out], [s.v for s in attn_out],
                        [s.conv for s in ssm_out],
                        [s.ssm for s in ssm_out])
            donate = (1, 2, 3, 4)
        else:
            def step(ps, convs, ssms, toks, pos, tok_seq, chunk_pos,
                     tok_valid, slot_ids, row_end, row_len, out_idx,
                     temps, top_ks, top_ps, rng_keys):
                build_slots.params = ps
                last, nxt, nxt_tok, _, ssm_out = build_slots(
                    None, None, convs, ssms, toks, pos, tok_seq,
                    chunk_pos, tok_valid, slot_ids, row_end, row_len,
                    None, out_idx, temps, top_ks, top_ps, rng_keys)
                return (last, nxt, nxt_tok,
                        [s.conv for s in ssm_out],
                        [s.ssm for s in ssm_out])
            donate = (1, 2)

        fn = self._ragged_jit_fn = jax.jit(step, donate_argnums=donate)
        return fn

    _RAGGED_ARG_NAMES_PURE = (
        "params", "conv_pools", "ssm_pools", "tokens", "positions",
        "token_seq", "chunk_pos", "tok_valid", "slot_ids", "row_end",
        "row_len", "out_idx", "temperatures", "top_ks", "top_ps",
        "rng_keys")
    _RAGGED_ARG_NAMES_HYBRID = (
        "params", "k_pages", "v_pages", "conv_pools", "ssm_pools",
        "tokens", "positions", "token_seq", "chunk_pos", "tok_valid",
        "slot_ids", "row_end", "row_len", "tok_pages", "tok_in_pages",
        "bounds", "page_table", "blk_pages", "blk_seq", "blk_start",
        "blk_n", "out_idx", "temperatures", "top_ks", "top_ps",
        "rng_keys")

    @staticmethod
    def _ragged_sig(cache, n_tokens, n_rows, width):
        return (int(n_tokens), int(n_rows), int(width)) \
            + tuple(cache.exec_signature())

    def _attn_block_geometry(self, cache, n_tokens, n_rows, width):
        """(QB, S) of the hybrid attention layers' q-block plan — same
        contract as gpt._ragged_block_geometry."""
        from ..ops.pallas.attention_core import choose_ragged_q_block
        paged = cache.paged
        fold = max(self.cfg.num_heads // paged.n_heads, 1)
        q_block = choose_ragged_q_block(int(n_tokens), fold)
        return int(n_tokens) // q_block, int(n_rows) * int(width)

    def ragged_arg_specs(self, cache, n_tokens, n_rows, width):
        """ShapeDtypeStructs of one ragged-step signature — what
        `warm_ragged` AOT-compiles ahead of traffic."""
        from ..jit.api import state_arrays
        params = getattr(self, "_paged_params", None)
        if params is None:
            params = self._paged_params = state_arrays(self)[0]
        sds = jax.ShapeDtypeStruct
        i32, f32 = jnp.int32, jnp.float32
        rec = getattr(cache, "recurrent", cache)
        S = rec.n_pages
        d, N, K = rec.d_inner, rec.d_state, rec.d_conv
        sdt = rec.conv[0].dtype
        convs = [sds((S, K - 1, d), sdt) for _ in range(rec.n_layers)]
        ssms = [sds((S, d, N), sdt) for _ in range(rec.n_layers)]
        T, B = int(n_tokens), int(n_rows)
        tok = lambda: sds((T,), i32)
        row = lambda: sds((B,), i32)
        pspec = jax.tree.map(lambda a: sds(a.shape, a.dtype), params)
        common_t = (tok(), tok(), tok(), tok(), sds((T,), f32))
        common_b = (row(), row(), row())
        sampling = (row(), sds((B,), f32), sds((B,), i32),
                    sds((B,), f32), sds((B, 2), jnp.uint32))
        if not self.ssm.hybrid:
            return (pspec, convs, ssms) + common_t + common_b + sampling
        paged = cache.paged
        pshape = (paged.n_pages, paged.page_size, paged.n_heads,
                  paged.head_dim)
        pools = [sds(pshape, paged.k[0].dtype)
                 for _ in range(paged.n_layers)]
        qb, s_cap = self._attn_block_geometry(cache, n_tokens, n_rows,
                                              width)
        return ((pspec, pools, list(pools), convs, ssms) + common_t
                + common_b
                + (tok(), tok(), tok(), sds((B, int(width)), i32),
                   sds((qb, s_cap), i32), sds((qb, s_cap), i32),
                   sds((qb, s_cap), i32), sds((qb,), i32))
                + sampling)

    def warm_ragged(self, cache, n_tokens, n_rows, width, inline=False):
        """Single-flight AOT compile of one ragged signature through
        the background warm pipeline (jit/warm.py) — same ledger tag
        and zero-new-executables discipline as the GPT step."""
        from ..jit import warm as _warm
        from ..jit.api import aot_compile
        exec_cache = getattr(self, "_ragged_exec", None)
        if exec_cache is None:
            exec_cache = self._ragged_exec = {}
        sig = self._ragged_sig(cache, n_tokens, n_rows, width)
        specs = self.ragged_arg_specs(cache, n_tokens, n_rows, width)
        jitted = self._ragged_jitted()
        names = self._RAGGED_ARG_NAMES_HYBRID if self.ssm.hybrid \
            else self._RAGGED_ARG_NAMES_PURE

        def thunk():
            return aot_compile(jitted, specs, tag=self.RAGGED_TAG,
                               arg_names=names)

        return _warm.submit_cached(exec_cache, sig, self.RAGGED_TAG,
                                   thunk, inline=inline)

    def paged_ragged_step(self, cache, rows, pad_to_tokens=None,
                          pad_to_rows=None, sampling=None,
                          return_per_token=False):
        """ONE continuous-batching step over mixed rows (decode rows
        carry one token, prefill-chunk rows a prompt slice), advanced
        in a single jitted program over the Pallas selective-scan
        kernel — each row's conv tail + state matrix gathered from its
        slot, updated, scattered back; pad tokens are identity state
        updates by construction. Same contract as
        gpt.paged_ragged_step (padded shapes pin the executable,
        `sampling` the per-row config, `return_per_token` the
        speculative verify lane — unused here: the recurrent strategy
        refuses speculation at engine construction)."""
        self._check_pools(cache)
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
        hybrid = self.ssm.hybrid
        rec = getattr(cache, "recurrent", cache)
        # the cache lock holds from the plan through the donated-pool
        # swap (see gpt._paged_decode_jit): another engine sharing the
        # pool must see pre- or post-step buffers, never the carcass
        with cache.lock:
            lens = [(s, len(t)) for s, t in rows]
            t_real = sum(n for _, n in lens)
            T = int(pad_to_tokens) if pad_to_tokens else max(t_real, 1)
            B = int(pad_to_rows) if pad_to_rows else max(len(rows), 1)
            plan = cache.plan_step(lens, pad_to_tokens=T, pad_to_rows=B)
            if hybrid:
                aplan = cache.plan_ragged(lens, pad_to_tokens=T,
                                          pad_to_rows=B,
                                          q_heads=self.cfg.num_heads)
                W = aplan["page_table"].shape[1]
            else:
                W = 1
            toks = np.zeros((T,), np.int32)
            off = 0
            for _, t in rows:
                toks[off:off + len(t)] = \
                    np.asarray(t, np.int32).reshape(-1)
                off += len(t)
            entry = getattr(self, "_ragged_exec", {}).get(
                self._ragged_sig(cache, T, B, W))
            if entry is None:
                entry = self.warm_ragged(cache, T, B, W,
                                         inline=True).result()
            compiled, _ = entry
            if sampling is None:
                sampling = (np.zeros((B,), np.float32),
                            np.zeros((B,), np.int32),
                            np.ones((B,), np.float32),
                            np.zeros((B, 2), np.uint32))
            temps, top_ks, top_ps, rng_keys = sampling
            common_t = (jnp.asarray(toks),
                        jnp.asarray(plan["positions"]),
                        jnp.asarray(plan["token_seq"]),
                        jnp.asarray(plan["chunk_pos"]),
                        jnp.asarray(plan["tok_valid"]))
            common_b = (jnp.asarray(plan["slot_ids"]),
                        jnp.asarray(plan["row_end"]),
                        jnp.asarray(plan["row_len"]))
            tail = (jnp.asarray(plan["out_idx"]), jnp.asarray(temps),
                    jnp.asarray(top_ks), jnp.asarray(top_ps),
                    jnp.asarray(rng_keys))
            if hybrid:
                args = ((params, list(cache.paged.k),
                         list(cache.paged.v), list(rec.conv),
                         list(rec.ssm)) + common_t + common_b
                        + (jnp.asarray(aplan["tok_pages"]),
                           jnp.asarray(aplan["tok_in_pages"]),
                           jnp.asarray(aplan["bounds"]),
                           jnp.asarray(aplan["page_table"]),
                           jnp.asarray(aplan["blk_pages"]),
                           jnp.asarray(aplan["blk_seq"]),
                           jnp.asarray(aplan["blk_start"]),
                           jnp.asarray(aplan["blk_n"])) + tail)
            else:
                args = ((params, list(rec.conv), list(rec.ssm))
                        + common_t + common_b + tail)
            try:
                out = compiled(*args)
            except Exception as e:
                # donation only consumes the pools once the program
                # EXECUTES; a dispatch failure before that leaves them
                # valid
                if not any(getattr(a, "is_deleted", lambda: False)()
                           for a in self._donated_pools(cache)):
                    raise
                self._poison(cache)
                raise RuntimeError(
                    "jitted ragged SSM step failed AFTER its state "
                    "pools were donated — this cache is unrecoverable; "
                    "rebuild it with make_paged_cache() and re-prefill "
                    "in-flight sequences") from e
            if hybrid:
                last, nxt, nxt_tok, new_k, new_v, new_c, new_s = out
                cache.paged.k = list(new_k)
                cache.paged.v = list(new_v)
            else:
                last, nxt, nxt_tok, new_c, new_s = out
            rec.conv = list(new_c)
            rec.ssm = list(new_s)
            for s, t in rows:
                cache.advance(s, len(t))
            n = plan["n_rows"]
        if return_per_token:
            return Tensor(last[:n]), nxt[:n], nxt_tok
        return Tensor(last[:n]), nxt[:n]


def ssm_tiny(vocab=1024):
    return SSMConfig(vocab_size=vocab, hidden_size=64, num_layers=2,
                     d_state=8, d_conv=4, expand=2,
                     max_position_embeddings=128)


def ssm_hybrid_tiny(vocab=1024):
    """Tiny hybrid: layer 1 of 2 is attention (attn_every=2)."""
    return SSMConfig(vocab_size=vocab, hidden_size=64, num_layers=2,
                     d_state=8, d_conv=4, expand=2, attn_every=2,
                     num_heads=4, max_position_embeddings=128)
