"""Plain reference of the SmallThinker decoder (PowerInfer/SmallThinker-
21BA3B-Instruct, config.json; report arXiv:2507.20984) as the
configuration file states it, for ONE chip's share of an expert-parallel
group: token embedding, pre-RMSNorm blocks (grouped-query attention,
then an expert layer whose router read the block's input), final
RMSNorm, untied output head. No kernels, no sort, no cache. Imports
nothing of the program; leaf names are the program's parameter names:
the leading layers that differ from the last one under
"model.lead.<i>.<leaf>", the uniform run that ends the stack stacked
under "model.h.*.<leaf>".

`x` is [tokens, hidden], layer l:
  router     r = x Wr over ALL routed experts, float32, from the
             block's INPUT, before any norm
  attention  h = rms(x); q = h Wq -> heads x head_dim; k = h Wk,
             v = h Wv -> kv heads x head_dim; where rope_layout[l] is 1,
             rotary over the whole head dim of q and k, else no
             positions at all; query head i on key/value head
             i // (heads / kv heads);
             softmax(q k^T / sqrt(head_dim)) v as a masked softmax over
             the whole [T, T] square: key j visible to query t iff
             j <= t and, where sliding_window_layout[l] is 1,
             t - j < sliding_window_size; x += heads Wo. No biases.
  experts    h2 = rms(x); chosen = top-k of r; w = softmax over the
             chosen logits (moe_primary_router_apply_softmax with
             norm_topk_prob: the softmax over all experts renormalised
             over the chosen); x += sum over chosen experts HELD HERE of
             w_e Wdown_e(relu(Wgate_e h2) * (Wup_e h2)): every held
             expert is applied to every token and the result masked by
             the token's weight for it (zero where it was not chosen).
             What the absent experts would add is left out, and that
             partial result goes on to the next layer. No shared expert.

Departures noted (the configuration file's `assumed`): the router reads
the un-normalised block input (the report's "pre-attention router");
the window's edge is t - j < window; the rotary pairs are the two halves
of the head dim; the report's secondary experts and sparse-ReLU
predictor are inference-time devices with no key in config.json and are
not built. Memory: batch rows, heads, experts and — where a token stands
alone (experts, the head) — blocks of TOKEN_BLOCK tokens are walked one
at a time (lax.map / lax.scan under jax.checkpoint), so one [T, T] score
square and one block's expert activations exist at a time."""
import jax
import jax.numpy as jnp

from .common import act_dtype, mm

LEAVES = ("input_layernorm.weight", "self_attn.q_proj.weight",
          "self_attn.k_proj.weight", "self_attn.v_proj.weight",
          "self_attn.o_proj.weight", "post_attention_layernorm.weight",
          "mlp.router.weight", "mlp.experts_gate", "mlp.experts_up",
          "mlp.experts_down")
TOKEN_BLOCK = 1024
# the embedding's seeded rows are N(0, 1), every other matrix N(0, 0.02)
# (`assumed.weights` says why: under rows of 0.02 the normed sublayers'
# outputs swamp a token's own vector after one layer, every token's
# router input is nearly the same vector and the deep layers route all
# tokens to the same experts)
EMBEDDING = "normal:1.0"


def layer_kinds(cfg):
    """[(rotary, window or None)] a layer, from the two published
    layouts' first num_hidden_layers entries."""
    n = cfg["num_hidden_layers"]
    return [(bool(r), cfg["sliding_window_size"] if s else None)
            for r, s in zip(cfg["rope_layout"][:n],
                            cfg["sliding_window_layout"][:n])]


def n_lead(cfg):
    """How many leading layers differ in kind from the last one's run."""
    kinds = layer_kinds(cfg)
    n = len(kinds)
    while n and kinds[n - 1] == kinds[-1]:
        n -= 1
    return n


def layer_shapes(cfg):
    H, hd = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    E, F = cfg["moe_num_primary_experts"], cfg["moe_ffn_hidden_size"]
    return {"input_layernorm.weight": (H,),
            "self_attn.q_proj.weight": (H, nh * hd),
            "self_attn.k_proj.weight": (H, nkv * hd),
            "self_attn.v_proj.weight": (H, nkv * hd),
            "self_attn.o_proj.weight": (nh * hd, H),
            "post_attention_layernorm.weight": (H,),
            "mlp.router.weight": (H, cfg["router_experts"]),
            "mlp.experts_gate": (E, H, F), "mlp.experts_up": (E, H, F),
            "mlp.experts_down": (E, F, H)}      # E: the experts HELD here


def _kind(name):
    return "ones" if "norm" in name else "normal"


def param_spec(cfg):
    """Exactly the program's trainable parameters; the rotary tables
    and the router's (zero) selection bias are in neither side's."""
    H, V = cfg["hidden_size"], cfg["vocab_size"]
    lead = n_lead(cfg)
    spec = {"model.embed_tokens.weight": ((V, H), EMBEDDING),
            "model.norm.weight": ((H,), "ones"),
            "lm_head.weight": ((H, V), "normal")}
    for n, s in layer_shapes(cfg).items():
        for i in range(lead):
            spec[f"model.lead.{i}.{n}"] = (s, _kind(n))
        spec[f"model.h.*.{n}"] = (
            (cfg["num_hidden_layers"] - lead,) + s, _kind(n))
    return spec


def rms_norm(x, g, eps, prec):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(act_dtype(prec))


def rotary(x, theta):
    """x [T, heads, dim]: position t rotates the pair (x[i], x[i + dim/2])
    by t * theta^(-2i/dim)."""
    T, half = x.shape[0], x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / (2 * half))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           -1).astype(x.dtype)


def attention(h, p, cfg, prec, kind):
    """One batch row: h [T, H], already normed -> [T, H]."""
    rope, window = kind
    T, hd, dt = h.shape[0], cfg["head_dim"], act_dtype(prec)
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    q = mm(h, p["self_attn.q_proj.weight"], prec).reshape(T, nh, hd)
    k = mm(h, p["self_attn.k_proj.weight"], prec).reshape(T, nkv, hd)
    v = mm(h, p["self_attn.v_proj.weight"], prec).reshape(T, nkv, hd)
    if rope:
        theta = float(cfg["rope_theta"])
        q, k = rotary(q, theta), rotary(k, theta)
    # query head i reads key/value head i // group
    k, v = (jnp.repeat(t, nh // nkv, axis=1) for t in (k, v))
    ahead = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
    mask = ahead >= 0
    if window is not None:
        mask &= ahead < window

    @jax.checkpoint
    def head(qkv):
        qh, kh, vh = qkv
        s = mm(qh, kh.T, prec).astype(jnp.float32) / (hd ** 0.5)
        a = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return mm(a.astype(dt), vh, prec)

    o = jax.lax.map(head, tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v)))
    o = jnp.moveaxis(o, 0, 1).reshape(T, nh * hd)
    return mm(o, p["self_attn.o_proj.weight"], prec)


def token_blocks(fn, x):
    """fn over x (an array [T, ...] or a tuple of them) a block of tokens
    at a time, rematerialised."""
    T = jax.tree.leaves(x)[0].shape[0]
    blk = TOKEN_BLOCK if T % TOKEN_BLOCK == 0 else T
    y = jax.lax.map(jax.checkpoint(fn), jax.tree.map(
        lambda a: a.reshape((T // blk, blk) + a.shape[1:]), x))
    return y.reshape((T,) + y.shape[2:])


def gated(x, wg, wu, wd, prec):
    return mm(jax.nn.relu(mm(x, wg, prec)) * mm(x, wu, prec), wd, prec)


def route(x, router_w, cfg):
    """[T, router_experts] float32: the token's weight for each routed
    expert, zero where it was not chosen."""
    r = mm(x, router_w, "f32")
    top, chosen = jax.lax.top_k(r, cfg["moe_num_active_primary_experts"])
    if cfg["moe_primary_router_apply_softmax"] and cfg["norm_topk_prob"]:
        picked = jax.nn.softmax(top, axis=-1)
    elif cfg["moe_primary_router_apply_softmax"]:
        picked = jnp.take_along_axis(jax.nn.softmax(r, axis=-1), chosen, -1)
    else:
        raise ValueError("only the softmax router is written down")
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(r).at[rows, chosen].set(picked)


def expert_layer(h2, router_in, p, cfg, prec, held=None):
    """The expert layer over tokens h2 [T, H], routed on router_in
    [T, H]. `held`: (first, count) of the routed experts whose weights p
    holds; the configuration's share by default. Experts one at a time,
    each over blocks of tokens."""
    first, count = held or (cfg["local_expert_start"],
                            cfg["moe_num_primary_experts"])
    w = route(router_in, p["mlp.router.weight"], cfg)[:, first:first + count]

    def one(y, e):
        wg, wu, wd, we = e
        out = token_blocks(
            lambda t: gated(t[0], wg, wu, wd, prec).astype(jnp.float32)
            * t[1][:, None], (h2, we))
        return y + out.astype(y.dtype), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h2),
                        (p["mlp.experts_gate"], p["mlp.experts_up"],
                         p["mlp.experts_down"], w.T))
    return y


def block(x, p, cfg, prec, kind):
    """One layer over x [B, T, H]: attention a batch row at a time, the
    expert layer over all tokens, routed on the layer's input."""
    eps = cfg["rms_norm_eps"]
    B, T, H = x.shape
    router_in = x.reshape(B * T, H)
    x = x + jax.lax.map(jax.checkpoint(lambda r: attention(
        rms_norm(r, p["input_layernorm.weight"], eps, prec), p, cfg, prec,
        kind)), x)
    h2 = rms_norm(x, p["post_attention_layernorm.weight"], eps,
                  prec).reshape(B * T, H)
    return x + expert_layer(h2, router_in, p, cfg, prec).reshape(B, T, H)


def forward(w, cfg, ids, prec="f32"):
    """Logits [B, T, V] (float32) of token ids [B, T], every layer
    rematerialised."""
    kinds, lead = layer_kinds(cfg), n_lead(cfg)
    x = w["model.embed_tokens.weight"][ids].astype(act_dtype(prec))
    for i in range(lead):
        p = {n: w[f"model.lead.{i}.{n}"] for n in LEAVES}
        x = jax.checkpoint(
            lambda h, p, kind=kinds[i]: block(h, p, cfg, prec, kind))(x, p)
    stacked = {n: w[f"model.h.*.{n}"] for n in LEAVES}
    x, _ = jax.lax.scan(jax.checkpoint(
        lambda h, p: (block(h, p, cfg, prec, kinds[-1]), None)), x, stacked)
    x = rms_norm(x, w["model.norm.weight"], cfg["rms_norm_eps"], prec)
    B, T, H = x.shape
    logits = token_blocks(
        lambda t: mm(t, w["lm_head.weight"], prec).astype(jnp.float32),
        x.reshape(B * T, H))
    return logits.reshape(B, T, -1)
