"""Plain reference of the rehearsal's block-diffusion model
(tests/data/diffusion_cell.json) as its configuration states it.
Straightforward jax.numpy; imports nothing of the program, nor of the
model that stands in for it (tests/diffusion_model.py); leaf names are
that model's parameter names.

forward(w, cfg, (noised, clean), prec) -> float32 logits [B, S, V] of
the noised positions, each to be scored against the clean token at its
place (references/common.py weighted_xent). The sequence is
x = [noised ; clean], 2S positions, both halves at positions 0..S-1:
x = E[ids] + P[positions]; each layer, with rms(x) = x / sqrt(mean(x^2)
+ eps) * g,
  attention  h = rms(x); q, k, v = h Wq, h Wk, h Wv in heads of
             hidden / heads; softmax(q k^T / sqrt(head dim)) v over the
             keys the BD3-LM mask shows each query (query i, key j, block
             b(i) = (i mod S) // block_length): a noised query sees the
             noised keys of its own block and the clean keys of earlier
             blocks, a clean query the clean keys of its own and earlier
             blocks; x += heads Wo
  mlp        h = rms(x); x += Wdown(silu(Wgate h) * (Wup h))
then rms(x) of the noised half, and the untied head."""
import jax
import jax.numpy as jnp

from benchmarks.references.common import act_dtype, mm

LAYER = {"input_layernorm.weight": "h", "q_proj.weight": "hh",
         "k_proj.weight": "hh", "v_proj.weight": "hh",
         "o_proj.weight": "hh", "post_attention_layernorm.weight": "h",
         "mlp.gate_proj.weight": "hf", "mlp.up_proj.weight": "hf",
         "mlp.down_proj.weight": "fh"}


def param_spec(cfg):
    H, V = cfg["hidden_size"], cfg["vocab_size"]
    dims = {"h": H, "f": cfg["intermediate_size"]}
    spec = {"embed_tokens.weight": ((V, H), "normal"),
            "embed_positions.weight": ((cfg["max_position_embeddings"], H),
                                       "normal"),
            "norm.weight": ((H,), "ones"),
            "lm_head.weight": ((H, V), "normal")}
    for i in range(cfg["num_hidden_layers"]):
        for n, shape in LAYER.items():
            spec[f"layers.{i}.{n}"] = (tuple(dims[c] for c in shape),
                                       "ones" if "norm" in n else "normal")
    return spec


def rms_norm(x, g, eps, prec):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(act_dtype(prec))


def visible(seq, block_length):
    """[2 seq, 2 seq] bool, query by key: the BD3-LM mask."""
    i = jnp.arange(2 * seq)
    b, clean = (i % seq) // block_length, i >= seq
    bq, bk = b[:, None], b[None, :]
    cq, ck = clean[:, None], clean[None, :]
    return jnp.where(cq, ck & (bk <= bq),
                     jnp.where(ck, bk < bq, bk == bq))


def layer(x, w, i, cfg, prec, mask):
    eps, nh = cfg["rms_norm_eps"], cfg["num_attention_heads"]
    B, T, H = x.shape
    p = lambda n: w[f"layers.{i}.{n}"]
    h = rms_norm(x, p("input_layernorm.weight"), eps, prec)
    q, k, v = (mm(h, p(f"{n}_proj.weight"), prec).reshape(B, T, nh, H // nh)
               for n in "qkv")
    s = mm(q, k, prec, "bqnd,bknd->bnqk").astype(jnp.float32) \
        / (H // nh) ** 0.5
    a = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    o = mm(a.astype(act_dtype(prec)), v, prec, "bnqk,bknd->bqnd")
    x = x + mm(o.reshape(B, T, H), p("o_proj.weight"), prec)
    h = rms_norm(x, p("post_attention_layernorm.weight"), eps, prec)
    up = jax.nn.silu(mm(h, p("mlp.gate_proj.weight"), prec)) \
        * mm(h, p("mlp.up_proj.weight"), prec)
    return x + mm(up, p("mlp.down_proj.weight"), prec)


def forward(w, cfg, batch, prec="f32"):
    noised, clean = batch
    S = noised.shape[1]
    ids = jnp.concatenate([noised, clean], axis=1)
    pos = jnp.concatenate([jnp.arange(S)] * 2)
    x = (w["embed_tokens.weight"][ids] + w["embed_positions.weight"][pos]
         ).astype(act_dtype(prec))
    mask = visible(S, cfg["block_length"])
    for i in range(cfg["num_hidden_layers"]):
        x = layer(x, w, i, cfg, prec, mask)
    x = rms_norm(x[:, :S], w["norm.weight"], cfg["rms_norm_eps"], prec)
    return mm(x, w["lm_head.weight"], prec).astype(jnp.float32)
