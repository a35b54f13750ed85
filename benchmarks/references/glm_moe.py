"""Plain reference of the GLM-4.7-Flash decoder (`glm4_moe_lite`, the
DeepSeek-V2/V3 form) as the configuration file states it, for ONE chip's
share of an expert-parallel group: token embedding, pre-RMSNorm blocks
(multi-head latent attention, then a SiLU-gated feed-forward in the
leading dense layers and an expert layer in the rest), final RMSNorm,
untied output head. No kernels, no sort, no cache. Imports nothing of the
program; leaf names are the program's parameter names, the expert layers
held stacked under "model.h.*.<leaf>", everything else unstacked.

`x` is [tokens, hidden]:
  block      x += attn(rms(x)); x += ffn(rms(x))
  attention  cq = rms(x Wqa); q = cq Wqb -> heads x (nope | rope)
             [ckv | kr] = x Wkva; [kn | v] = rms(ckv) Wkvb -> heads x
             (nope | v); rotary on q's rope part and on kr, which all
             heads share; k = kn | kr; causal softmax(q k^T / sqrt(nope +
             rope)) v as a masked softmax over the whole [T, T] square;
             o = heads Wo. No biases.
  dense ffn  Wd(silu(Wg x) * (Wu x))
  experts    s = sigmoid(x Wr) over ALL routed experts, float32;
             chosen = top-k of s + b (b, the selection bias, is zero and
             is no parameter); w = s[chosen] / (sum + 1e-20) * scale;
             y = shared(x) + sum over chosen experts HELD HERE of
             w_e E_e(x): every held expert is applied to every token and
             the result masked by the token's weight for it (zero where
             it was not chosen). What the absent experts would add is
             left out, and that partial y goes on to the next layer.

Departures noted: the rotary pairs are the two halves of the 64 rope
dims (`assumed.rotary_pairing`); the next-token-prediction layer is not
built (`assumed`). Memory: batch rows, heads, experts and — where a
token stands alone (feed-forwards, the head) — blocks of TOKEN_BLOCK
tokens are walked one at a time (lax.map / lax.scan under
jax.checkpoint), so one [T, T] score square and one block's expert
activations exist at a time."""
import jax
import jax.numpy as jnp

from .common import act_dtype, mm

ATTN_LEAVES = ("input_layernorm.weight", "self_attn.q_a_proj.weight",
               "self_attn.q_a_layernorm.weight", "self_attn.q_b_proj.weight",
               "self_attn.kv_a_proj_with_mqa.weight",
               "self_attn.kv_a_layernorm.weight",
               "self_attn.kv_b_proj.weight", "self_attn.o_proj.weight",
               "post_attention_layernorm.weight")
DENSE_LEAVES = ATTN_LEAVES + ("mlp.gate_proj.weight", "mlp.up_proj.weight",
                              "mlp.down_proj.weight")
TOKEN_BLOCK = 1024
MOE_LEAVES = ATTN_LEAVES + (
    "mlp.router.weight", "mlp.experts_gate", "mlp.experts_up",
    "mlp.experts_down", "mlp.shared.gate_proj.weight",
    "mlp.shared.up_proj.weight", "mlp.shared.down_proj.weight")


def dims(cfg):
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    return {"H": cfg["hidden_size"], "nh": cfg["num_attention_heads"],
            "nope": nope, "rope": rope, "qk": nope + rope,
            "v": cfg["v_head_dim"], "rq": cfg["q_lora_rank"],
            "rkv": cfg["kv_lora_rank"]}


def layer_shapes(cfg, moe):
    d = dims(cfg)
    H, nh = d["H"], d["nh"]
    F, Fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    s = {"input_layernorm.weight": (H,),
         "self_attn.q_a_proj.weight": (H, d["rq"]),
         "self_attn.q_a_layernorm.weight": (d["rq"],),
         "self_attn.q_b_proj.weight": (d["rq"], nh * d["qk"]),
         "self_attn.kv_a_proj_with_mqa.weight": (H, d["rkv"] + d["rope"]),
         "self_attn.kv_a_layernorm.weight": (d["rkv"],),
         "self_attn.kv_b_proj.weight": (d["rkv"], nh * (d["nope"] + d["v"])),
         "self_attn.o_proj.weight": (nh * d["v"], H),
         "post_attention_layernorm.weight": (H,)}
    if not moe:
        s.update({"mlp.gate_proj.weight": (H, F), "mlp.up_proj.weight": (H, F),
                  "mlp.down_proj.weight": (F, H)})
        return s
    E = cfg["n_routed_experts"]            # the experts HELD here
    Fs = Fe * cfg["n_shared_experts"]
    s.update({"mlp.router.weight": (H, cfg["router_experts"]),
              "mlp.experts_gate": (E, H, Fe), "mlp.experts_up": (E, H, Fe),
              "mlp.experts_down": (E, Fe, H),
              "mlp.shared.gate_proj.weight": (H, Fs),
              "mlp.shared.up_proj.weight": (H, Fs),
              "mlp.shared.down_proj.weight": (Fs, H)})
    return s


def _kind(name):
    return "ones" if "layernorm" in name or name.endswith("norm.weight") \
        else "normal"


def param_spec(cfg):
    """Exactly the program's trainable parameters: the leading dense
    layers under "model.lead.<i>.", the expert layers stacked under
    "model.h.*."; the selection bias and the rotary tables are in
    neither side's parameters."""
    H, V = cfg["hidden_size"], cfg["vocab_size"]
    n_dense = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - n_dense
    spec = {"model.embed_tokens.weight": ((V, H), "normal"),
            "model.norm.weight": ((H,), "ones"),
            "lm_head.weight": ((H, V), "normal")}
    for i in range(n_dense):
        for n, s in layer_shapes(cfg, False).items():
            spec[f"model.lead.{i}.{n}"] = (s, _kind(n))
    for n, s in layer_shapes(cfg, True).items():
        spec[f"model.h.*.{n}"] = ((n_moe,) + s, _kind(n))
    return spec


def rms_norm(x, g, eps, prec):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(act_dtype(prec))


def rotary(x, theta):
    """x [T, ..., rope]: position t rotates the pair (x[i], x[i + rope/2])
    by t * theta^(-2i/rope)."""
    T, half = x.shape[0], x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / (2 * half))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    shape = (T,) + (1,) * (x.ndim - 2) + (half,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           -1).astype(x.dtype)


def attention(x, p, cfg, prec):
    """One batch row: x [T, H] -> [T, H]."""
    d, eps, dt = dims(cfg), cfg["rms_norm_eps"], act_dtype(prec)
    T, nh = x.shape[0], d["nh"]
    cq = rms_norm(mm(x, p["self_attn.q_a_proj.weight"], prec),
                  p["self_attn.q_a_layernorm.weight"], eps, prec)
    q = mm(cq, p["self_attn.q_b_proj.weight"], prec).reshape(T, nh, d["qk"])
    kva = mm(x, p["self_attn.kv_a_proj_with_mqa.weight"], prec)
    ckv, kr = kva[:, :d["rkv"]], kva[:, d["rkv"]:]
    kv = mm(rms_norm(ckv, p["self_attn.kv_a_layernorm.weight"], eps, prec),
            p["self_attn.kv_b_proj.weight"], prec).reshape(
                T, nh, d["nope"] + d["v"])
    theta = float(cfg["rope_theta"])
    q = jnp.concatenate([q[..., :d["nope"]],
                         rotary(q[..., d["nope"]:], theta)], -1)
    kr = rotary(kr, theta)
    k = jnp.concatenate([kv[..., :d["nope"]],
                         jnp.broadcast_to(kr[:, None], (T, nh, d["rope"]))],
                        -1)
    v = kv[..., d["nope"]:]
    mask = jnp.tril(jnp.ones((T, T), bool))

    @jax.checkpoint
    def head(qkv):
        qh, kh, vh = qkv
        s = mm(qh, kh.T, prec).astype(jnp.float32) / (d["qk"] ** 0.5)
        a = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return mm(a.astype(dt), vh, prec)

    o = jax.lax.map(head, tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v)))
    o = jnp.moveaxis(o, 0, 1).reshape(T, nh * d["v"])
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
    return mm(jax.nn.silu(mm(x, wg, prec)) * mm(x, wu, prec), wd, prec)


def route(x, router_w, cfg):
    """[T, router_experts] float32: the token's weight for each routed
    expert, zero where it was not chosen."""
    s = jax.nn.sigmoid(mm(x, router_w, "f32"))
    _, chosen = jax.lax.top_k(s, cfg["num_experts_per_tok"])   # bias is 0
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    picked = picked * cfg["routed_scaling_factor"]
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(picked)


def expert_layer(x, p, cfg, prec, held=None):
    """The expert layer over tokens x [T, H]. `held`: (first, count) of the
    routed experts whose weights p holds; the configuration's share by
    default. Experts one at a time, each over blocks of tokens."""
    first, count = held or (cfg["local_expert_start"],
                            cfg["n_routed_experts"])
    w = route(x, p["mlp.router.weight"], cfg)[:, first:first + count]
    y = token_blocks(
        lambda t: gated(t, p["mlp.shared.gate_proj.weight"],
                        p["mlp.shared.up_proj.weight"],
                        p["mlp.shared.down_proj.weight"], prec), x)

    def one(y, e):
        wg, wu, wd, we = e
        out = token_blocks(
            lambda t: gated(t[0], wg, wu, wd, prec).astype(jnp.float32)
            * t[1][:, None], (x, we))
        return y + out.astype(y.dtype), None

    y, _ = jax.lax.scan(one, y, (p["mlp.experts_gate"], p["mlp.experts_up"],
                                 p["mlp.experts_down"], w.T))
    return y


def block(x, p, cfg, prec, moe):
    """One layer over x [B, T, H]: attention a batch row at a time, the
    feed-forward over all tokens."""
    eps = cfg["rms_norm_eps"]
    x = x + jax.lax.map(jax.checkpoint(lambda r: attention(
        rms_norm(r, p["input_layernorm.weight"], eps, prec), p, cfg, prec)),
        x)
    B, T, H = x.shape
    h = rms_norm(x, p["post_attention_layernorm.weight"], eps,
                 prec).reshape(B * T, H)
    if moe:
        y = expert_layer(h, p, cfg, prec)
    else:
        y = token_blocks(
            lambda t: gated(t, p["mlp.gate_proj.weight"],
                            p["mlp.up_proj.weight"],
                            p["mlp.down_proj.weight"], prec), h)
    return x + y.reshape(B, T, H)


def forward(w, cfg, ids, prec="f32"):
    """Logits [B, T, V] (float32) of token ids [B, T], every layer
    rematerialised."""
    x = w["model.embed_tokens.weight"][ids].astype(act_dtype(prec))
    for i in range(cfg["first_k_dense_replace"]):
        p = {n: w[f"model.lead.{i}.{n}"] for n in DENSE_LEAVES}
        x = jax.checkpoint(lambda h, p: block(h, p, cfg, prec, False))(x, p)
    stacked = {n: w[f"model.h.*.{n}"] for n in MOE_LEAVES}
    x, _ = jax.lax.scan(jax.checkpoint(
        lambda h, p: (block(h, p, cfg, prec, True), None)), x, stacked)
    x = rms_norm(x, w["model.norm.weight"], cfg["rms_norm_eps"], prec)
    B, T, H = x.shape
    logits = token_blocks(
        lambda t: mm(t, w["lm_head.weight"], prec).astype(jnp.float32),
        x.reshape(B * T, H))
    return logits.reshape(B, T, -1)
