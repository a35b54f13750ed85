"""A decoder-only language model assembled from per-layer specs.

The stack is data: `DecoderConfig.layers` is a list of (mixer,
feed-forward) names, one pair a layer, looked up in MIXERS and FFNS; the
norm is a name too. Today's parts are what two families need. The
GLM-4.x / DeepSeek-V3 one: latent attention with rotary positions
("mla"), a SiLU-gated feed-forward ("dense"), the dropless expert layer
("moe": incubate.moe.DroplessMoE, sigmoid scores). SmallThinker:
grouped-query attention in two specs — "gqa_full", every earlier key and
no positions at all, and "gqa_window", a window of `sliding_window_size`
keys with rotary positions — and "moe_pre", the same expert layer with a
softmax over the chosen logits, ReLU gating and a router that reads the
LAYER'S INPUT, before attention. Positions belong to a mixer's spec, so
they differ by layer. This is where models/gpt.py's and models/ssm.py's
blocks are to move (ROADMAP D3): a new architecture is a new entry in a
table and a preset, not a third hand-written model file.

Block: x += mixer(norm(x)); x += ffn(norm(x)); final norm; untied head.
Parameter names follow the published module tree (`model.embed_tokens`,
`self_attn.q_a_proj`, `self_attn.q_proj`, `mlp.down_proj`, `lm_head`,
...). The layers stand
in two lists: `model.lead.<i>`, the leading layers that differ from the
rest, and `model.h.<i>`, the uniform run that ends the stack. Under a
trace every layer is rematerialised in the backward pass
(jax.checkpoint) from its input and the flash kernel's output (`out`,
`lse`), so that the kernel runs once a layer, and a uniform run of more
than one layer is one lax.scan over its stacked parameters, traced and
compiled once whatever its length.

A pattern that repeats with a period over one layer (SmallThinker's
full, window, window, window) is NOT one scan over periods: everything
before the last uniform run is unrolled (ROADMAP C10).

Training only: no decode cache yet (ROADMAP C9: a latent paged cache with
an absorbed decode path; C11: window layers and grouped key/value heads
in serving).
"""
import functools

import jax
import jax.numpy as jnp

from ..framework.core import Tensor
from .. import nn
from ..nn import functional as F

__all__ = ["DecoderConfig", "DecoderStack", "DecoderForCausalLM",
           "LatentAttention", "GroupedQueryAttention", "glm_4_7_flash_ep8",
           "smallthinker_21b_ep8"]


class DecoderConfig:
    """Field names are published configs' (transformers' `glm4_moe_lite`
    / `deepseek_v3`; `num_key_value_heads`, `head_dim` and
    `sliding_window_size`, which the grouped-query mixers read, as
    SmallThinker's config.json has them), so that a configuration file
    can be held against this object key by key. Where two families name
    one thing differently the field keeps the first's name and the
    other's preset maps onto it (`moe_intermediate_size`,
    `n_routed_experts`, `num_experts_per_tok`: SmallThinker's
    `moe_ffn_hidden_size`, `moe_num_primary_experts`,
    `moe_num_active_primary_experts`). Beside them: `router_experts`,
    the router's width (all routed experts of the model), where
    `n_routed_experts` counts those HELD here, from `local_expert_start`
    on; `router_scoring` and `hidden_act`, the expert layer's scores and
    gate (incubate.moe.DroplessMoE's `scoring`, `activation`); `layers`,
    the per-layer specs (by default `first_k_dense_replace` dense
    layers, then expert layers, all with latent attention); `norm`;
    `positions`, which the latent mixer checks (the grouped-query specs
    carry their own)."""

    def __init__(self, vocab_size=1024, hidden_size=128,
                 intermediate_size=512, num_hidden_layers=2,
                 num_attention_heads=4, q_lora_rank=64, kv_lora_rank=32,
                 qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=32,
                 rope_theta=10000.0, rms_norm_eps=1e-5,
                 moe_intermediate_size=64, n_routed_experts=0,
                 router_experts=None, local_expert_start=0,
                 n_shared_experts=0, num_experts_per_tok=2,
                 routed_scaling_factor=1.0, norm_topk_prob=True,
                 first_k_dense_replace=None, layers=None, norm="rms",
                 positions="rotary", initializer_range=0.02,
                 num_key_value_heads=None, head_dim=None,
                 sliding_window_size=None, router_scoring="sigmoid",
                 hidden_act="silu"):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        if num_key_value_heads is not None:
            # a field only where it is stated: the latent mixer has no
            # such count, and a file's key is held against this object
            self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim or hidden_size // num_attention_heads
        self.sliding_window_size = sliding_window_size
        self.router_scoring = router_scoring
        self.hidden_act = hidden_act
        self.q_lora_rank = q_lora_rank
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.rope_theta = rope_theta
        self.rms_norm_eps = rms_norm_eps
        self.moe_intermediate_size = moe_intermediate_size
        self.n_routed_experts = n_routed_experts
        self.router_experts = router_experts or n_routed_experts
        self.local_expert_start = local_expert_start
        self.n_shared_experts = n_shared_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.routed_scaling_factor = routed_scaling_factor
        self.norm_topk_prob = norm_topk_prob
        if first_k_dense_replace is None:
            first_k_dense_replace = 0 if n_routed_experts \
                else num_hidden_layers
        self.first_k_dense_replace = first_k_dense_replace
        if layers is None:
            layers = [("mla", "dense" if i < first_k_dense_replace
                       else "moe") for i in range(num_hidden_layers)]
        if len(layers) != num_hidden_layers:
            raise ValueError(f"{len(layers)} layer specs for "
                             f"num_hidden_layers={num_hidden_layers}")
        self.layers = [tuple(spec) for spec in layers]
        self.norm = norm
        self.positions = positions
        self.initializer_range = initializer_range


def _linear(n_in, n_out, cfg):
    return nn.Linear(n_in, n_out, bias_attr=False,
                     weight_attr=nn.initializer.Normal(
                         0.0, cfg.initializer_range))


class LatentAttention(nn.Layer):
    """Multi-head latent attention (DeepSeek-V2): queries and keys/values
    go through low-rank latents, and a slice of each query and ONE key
    slice shared by all heads carry the rotary positions.

        cq = norm(x Wqa);  q = cq Wqb          -> heads x (nope | rope)
        [ckv | kr] = x Wkva
        [kn | v] = norm(ckv) Wkvb              -> heads x (nope | v)
        k = kn | rotary(kr);  q's rope part rotated alike
        o = causal softmax(q k^T / sqrt(nope + rope)) v;  out = o Wo

    No biases. In training the core is plain causal attention at head
    dim nope + rope, through F.scaled_dot_product_attention (the flash
    kernels on the chip); a value head narrower than that is zero-padded
    to it and the padding cut from the result."""

    def __init__(self, cfg):
        super().__init__()
        if cfg.positions != "rotary":
            raise ValueError(f"LatentAttention: positions="
                             f"{cfg.positions!r} is not built")
        H, nh = cfg.hidden_size, cfg.num_attention_heads
        self.nh, self.nope, self.rope, self.vd = \
            nh, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        self.rkv = cfg.kv_lora_rank
        qk = self.nope + self.rope
        if self.vd > qk:
            raise ValueError(f"v_head_dim={self.vd} over the query/key "
                             f"head dim {qk}")
        norm = NORMS[cfg.norm]
        self.q_a_proj = _linear(H, cfg.q_lora_rank, cfg)
        self.q_a_layernorm = norm(cfg.q_lora_rank, cfg)
        self.q_b_proj = _linear(cfg.q_lora_rank, nh * qk, cfg)
        self.kv_a_proj_with_mqa = _linear(H, self.rkv + self.rope, cfg)
        self.kv_a_layernorm = norm(self.rkv, cfg)
        self.kv_b_proj = _linear(self.rkv, nh * (self.nope + self.vd), cfg)
        self.o_proj = _linear(nh * self.vd, H, cfg)
        self.rotary = nn.RotaryEmbedding(self.rope, cfg.rope_theta)

    def forward(self, x):
        from ..tensor.manipulation import concat, split
        B, T, _ = x.shape
        nh, nope, rope, vd = self.nh, self.nope, self.rope, self.vd
        with jax.named_scope("mla.project"):
            q = self.q_b_proj(self.q_a_layernorm(self.q_a_proj(x)))
            q_nope, q_rope = split(q.reshape([B, T, nh, nope + rope]),
                                   [nope, rope], axis=-1)
            ckv, kr = split(self.kv_a_proj_with_mqa(x), [self.rkv, rope],
                            axis=-1)
            kv = self.kv_b_proj(self.kv_a_layernorm(ckv))
            k_nope, v = split(kv.reshape([B, T, nh, nope + vd]),
                              [nope, vd], axis=-1)
            kr = self.rotary(kr.reshape([B, T, 1, rope]))
            q = concat([q_nope, self.rotary(q_rope)], axis=-1)
            k = concat([k_nope, kr.expand([B, T, nh, rope])], axis=-1)
            if vd < nope + rope:
                v = concat([v, Tensor(jnp.zeros(
                    (B, T, nh, nope + rope - vd), v.value.dtype))], axis=-1)
        with jax.named_scope("mla.core"):
            o = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        if vd < nope + rope:
            o = split(o, [vd, nope + rope - vd], axis=-1)[0]
        return self.o_proj(o.reshape([B, T, nh * vd]))


class GroupedQueryAttention(nn.Layer):
    """Grouped-query attention (Ainslie et al., 2023): `num_attention_
    heads` query heads of `head_dim` on `num_key_value_heads` key/value
    heads, query head i on key/value head i // group. No biases.

        q = x Wq -> heads x head_dim;  k = x Wk, v = x Wv -> kv heads x
        head_dim;  positions "rotary": q, k rotated over the whole head
        dim, "none": no positions at all (the causal mask is the only
        order the layer sees)
        o = softmax(q k^T / sqrt(head_dim)) v over the keys j <= t and,
        with `window`, t - j < sliding_window_size;  out = o Wo

    The core goes through F.scaled_dot_product_attention with k, v at
    their own head count: on the chip the flash kernels pick a query
    head's key/value head by their index maps and skip what lies behind
    the window; nothing is repeated to the query heads' count."""

    def __init__(self, cfg, window=False, positions="none"):
        super().__init__()
        if positions not in ("none", "rotary"):
            raise ValueError(f"GroupedQueryAttention: positions="
                             f"{positions!r} is not built")
        if window and not cfg.sliding_window_size:
            raise ValueError("a window layer needs sliding_window_size")
        H = cfg.hidden_size
        self.nh, self.hd = cfg.num_attention_heads, cfg.head_dim
        self.nkv = getattr(cfg, "num_key_value_heads", self.nh)
        if self.nh % self.nkv:
            raise ValueError(f"{self.nh} query heads on {self.nkv} "
                             "key/value heads")
        self.window = cfg.sliding_window_size if window else None
        self.q_proj = _linear(H, self.nh * self.hd, cfg)
        self.k_proj = _linear(H, self.nkv * self.hd, cfg)
        self.v_proj = _linear(H, self.nkv * self.hd, cfg)
        self.o_proj = _linear(self.nh * self.hd, H, cfg)
        self.rotary = nn.RotaryEmbedding(self.hd, cfg.rope_theta) \
            if positions == "rotary" else None

    def forward(self, x):
        B, T, _ = x.shape
        with jax.named_scope("gqa.project"):
            q = self.q_proj(x).reshape([B, T, self.nh, self.hd])
            k = self.k_proj(x).reshape([B, T, self.nkv, self.hd])
            v = self.v_proj(x).reshape([B, T, self.nkv, self.hd])
            if self.rotary is not None:
                q, k = self.rotary(q), self.rotary(k)
        with jax.named_scope("gqa.core"):
            o = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                               window=self.window)
        return self.o_proj(o.reshape([B, T, self.nh * self.hd]))


def _rms(width, cfg):
    return nn.RMSNorm(width, epsilon=cfg.rms_norm_eps)


def _dense_ffn(cfg):
    return nn.GatedMLP(cfg.hidden_size, cfg.intermediate_size,
                       weight_attr=nn.initializer.Normal(
                           0.0, cfg.initializer_range))


def _moe_ffn(cfg):
    from ..incubate.moe import DroplessMoE
    first = cfg.local_expert_start
    return DroplessMoE(
        cfg.hidden_size, cfg.moe_intermediate_size, cfg.router_experts,
        cfg.num_experts_per_tok,
        local_experts=range(first, first + cfg.n_routed_experts),
        n_shared_experts=cfg.n_shared_experts,
        routed_scaling_factor=cfg.routed_scaling_factor,
        norm_topk_prob=cfg.norm_topk_prob,
        weight_attr=nn.initializer.Normal(0.0, cfg.initializer_range),
        scoring=cfg.router_scoring, activation=cfg.hidden_act)


# the tables a layer spec is looked up in
MIXERS = {"mla": LatentAttention,
          "gqa_full": GroupedQueryAttention,
          "gqa_window": functools.partial(GroupedQueryAttention, window=True,
                                          positions="rotary")}
FFNS = {"dense": _dense_ffn, "moe": _moe_ffn, "moe_pre": _moe_ffn}
NORMS = {"rms": _rms}
# feed-forwards whose router reads the layer's input, not their own
ROUTED_ON_LAYER_INPUT = ("moe_pre",)

# what a layer keeps for its backward pass beside its input: the flash
# kernel's output and row statistics (named in ops/pallas/
# flash_attention.py), so that the backward pass rebuilds the
# projections and the feed-forward but does not run the kernel again
_REMAT_POLICY = jax.checkpoint_policies.save_only_these_names(
    "flash_out", "flash_lse")


class DecoderLayer(nn.Layer):
    def __init__(self, cfg, spec):
        super().__init__()
        mixer, ffn = spec
        norm = NORMS[cfg.norm]
        self.input_layernorm = norm(cfg.hidden_size, cfg)
        self.self_attn = MIXERS[mixer](cfg)
        self.post_attention_layernorm = norm(cfg.hidden_size, cfg)
        self.mlp = FFNS[ffn](cfg)
        self.routes_on_input = ffn in ROUTED_ON_LAYER_INPUT

    def forward(self, x):
        h = x + self.self_attn(self.input_layernorm(x))
        y = self.post_attention_layernorm(h)
        return h + (self.mlp(y, router_input=x) if self.routes_on_input
                    else self.mlp(y))


def _call_layer(layer, h, names):
    """(output, counter vector or None) of one layer on the array h, as
    a function of arrays: what jax.checkpoint and lax.scan can wrap. The
    counters a sublayer recorded leave the inner trace here; what they
    are called goes into the list `names`."""
    from ..jit.api import take_step_counters
    out = layer(Tensor(h)).value
    counters = take_step_counters(layer)
    if counters is None:
        return out, None
    names[:] = counters[0]
    return out, counters[1]


class DecoderStack(nn.Layer):
    """Embedding, the layers, the final norm. `lead` holds the leading
    layers whose spec differs from the last one's, `h` the uniform run
    that ends the stack."""

    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=nn.initializer.Normal(0.0, cfg.initializer_range))
        n_lead = len(cfg.layers)
        while n_lead and cfg.layers[n_lead - 1] == cfg.layers[-1]:
            n_lead -= 1
        self.lead = nn.LayerList([DecoderLayer(cfg, s)
                                  for s in cfg.layers[:n_lead]])
        self.h = nn.LayerList([DecoderLayer(cfg, s)
                               for s in cfg.layers[n_lead:]])
        self.norm = NORMS[cfg.norm](cfg.hidden_size, cfg)
        self.step_counter_names = ()
        self._step_counters = None

    def forward(self, input_ids):
        from ..jit.api import take_step_counters
        x = self.embed_tokens(input_ids)
        self._step_counters = None
        if not isinstance(x.value, jax.core.Tracer):
            # eager: Tensors all the way, so that the tape sees every op
            for layer in [*self.lead, *self.h]:
                x = layer(x)
            counters = take_step_counters(self)
            if counters is not None:
                self.step_counter_names, self._step_counters = counters
            return self.norm(x)
        h, names, found = x.value, [], []
        scans = len(self.h) > 1
        for layer in [*self.lead, *([] if scans else self.h)]:
            h, c = jax.checkpoint(
                lambda hv, layer=layer: _call_layer(layer, hv, names),
                policy=_REMAT_POLICY)(h)
            found.append(c)
        if scans:
            h, c = self._scan(h, names)
            found.append(None if c is None else c.sum(0, dtype=c.dtype))
        found = [c for c in found if c is not None]
        if found:
            self.step_counter_names = tuple(names)
            self._step_counters = sum(found)
        return self.norm(Tensor(h))

    def _scan(self, h, names):
        """The uniform run as ONE lax.scan over its stacked parameters
        and buffers (stacked here, under the trace: at rest they stay
        per layer, as state_dict and the optimizer see them)."""
        from ..jit.api import _bind, _restore
        proto = self.h[0]
        dicts = [{**dict(b.named_parameters()), **dict(b.named_buffers())}
                 for b in self.h]
        stacked = {k: jnp.stack([d[k].value for d in dicts])
                   for k in dicts[0]}

        def step(hv, arrays):
            saved = _bind(proto, arrays)
            try:
                return _call_layer(proto, hv, names)
            finally:
                _restore(saved)

        return jax.lax.scan(
            jax.checkpoint(step, prevent_cse=False, policy=_REMAT_POLICY),
            h, stacked)


class DecoderForCausalLM(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        self.model = DecoderStack(cfg)
        self.lm_head = _linear(cfg.hidden_size, cfg.vocab_size, cfg)

    def forward(self, input_ids):
        return self.lm_head(self.model(input_ids))

    def loss(self, input_ids, labels):
        logits = self(input_ids)
        V = logits.shape[-1]
        return F.cross_entropy(logits.reshape([-1, V]),
                               labels.reshape([-1]))


def glm_4_7_flash_ep8(**overrides):
    """zai-org/GLM-4.7-Flash (30B-A3B; config.json, `glm4_moe_lite`) as
    ONE chip's share of an 8-way expert-parallel group holds it, every
    width as published: hidden 2048; latent attention with 20 heads of
    192 + 64 query/key and 256 value dims over latents of 768 and 512;
    a dense layer of width 10,240, then expert layers whose router is 64
    wide, 4 a token, scale 1.8, one shared expert, experts of width
    1536. The share: experts 0-7 of the 64, rows 0-19,359 of the 154,880
    of the vocabulary, the dense layer and 4 of the 46 expert layers
    (benchmarks/configs/glm-4.7-flash-ep8.json states the deployment)."""
    kw = dict(vocab_size=19360, hidden_size=2048, intermediate_size=10240,
              num_hidden_layers=5, first_k_dense_replace=1,
              num_attention_heads=20, q_lora_rank=768, kv_lora_rank=512,
              qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
              rope_theta=1000000, rms_norm_eps=1e-5,
              moe_intermediate_size=1536, n_routed_experts=8,
              router_experts=64, local_expert_start=0, n_shared_experts=1,
              num_experts_per_tok=4, routed_scaling_factor=1.8,
              norm_topk_prob=True)
    kw.update(overrides)
    return DecoderConfig(**kw)


def smallthinker_21b_ep8(**overrides):
    """PowerInfer/SmallThinker-21BA3B-Instruct (config.json; report
    arXiv:2507.20984) as ONE chip's share of an 8-way expert-parallel
    group holds it, every width as published: hidden 2560; 28 query heads
    of 128 on 4 key/value heads; layers in periods of four — one with
    full causal attention and no positions, three with a window of 4,096
    keys and rotary positions (theta 1.5e6) — each with 64 ReLU-gated
    experts of width 768, 6 a token, weighted by a softmax over the
    chosen logits of a router that reads the layer's input; no shared
    expert, no dense layer. The share: experts 0-7 of the 64, rows
    0-18,991 of the 151,936 of the vocabulary, one period of the 13
    (benchmarks/configs/smallthinker-21b-ep8.json states the
    deployment)."""
    kw = dict(vocab_size=18992, hidden_size=2560, num_hidden_layers=4,
              num_attention_heads=28, num_key_value_heads=4, head_dim=128,
              sliding_window_size=4096, rope_theta=1500000,
              rms_norm_eps=1e-6, moe_intermediate_size=768,
              n_routed_experts=8, router_experts=64, local_expert_start=0,
              n_shared_experts=0, num_experts_per_tok=6,
              routed_scaling_factor=1.0, norm_topk_prob=True,
              router_scoring="softmax_topk", hidden_act="relu")
    kw.update(overrides)
    # rope_layout = sliding_window_layout = [0, 1, 1, 1] x 13
    kw.setdefault("layers", [
        ("gqa_window" if i % 4 else "gqa_full", "moe_pre")
        for i in range(kw["num_hidden_layers"])])
    return DecoderConfig(**kw)
