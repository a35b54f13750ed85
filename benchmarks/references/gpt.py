"""Plain reference of the GPT-2 / GPT-3 decoder as the configuration
files state it: learned token and position embeddings, pre-LayerNorm
blocks (causal multi-head attention, then a tanh-GELU MLP), final
LayerNorm, output head tied to the token embedding. No kernels, no
cache, no batching tricks. Imports nothing of the program; leaf names
follow the published module tree so that the benchmark can hand the
same seeded leaves to both sides; the leaves of the layers are held
stacked, "gpt.h.*.<leaf>" of shape [layers, ...].

Departure noted: the fused qkv weight is laid out [hidden, (3, heads,
head_dim)] — q, k, v as the slowest axis — which is how the program and
GPT-2's own c_attn hold it."""
import jax
import jax.numpy as jnp

from .common import act_dtype, layer_norm, mm

LAYER_LEAVES = ("ln_1.weight", "ln_1.bias", "attn.qkv_proj.weight",
                "attn.qkv_proj.bias", "attn.out_proj.weight",
                "attn.out_proj.bias", "ln_2.weight", "ln_2.bias",
                "mlp.fc_in.weight", "mlp.fc_in.bias", "mlp.fc_out.weight",
                "mlp.fc_out.bias")


def param_spec(cfg):
    H, F, V = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    shapes = {"ln_1.weight": (H,), "ln_1.bias": (H,),
              "attn.qkv_proj.weight": (H, 3 * H),
              "attn.qkv_proj.bias": (3 * H,),
              "attn.out_proj.weight": (H, H), "attn.out_proj.bias": (H,),
              "ln_2.weight": (H,), "ln_2.bias": (H,),
              "mlp.fc_in.weight": (H, F), "mlp.fc_in.bias": (F,),
              "mlp.fc_out.weight": (F, H), "mlp.fc_out.bias": (H,)}
    kind = lambda n: "ones" if n.startswith("ln") and n.endswith("weight") \
        else "normal"
    spec = {"gpt.wte.weight": ((V, H), "normal"),
            "gpt.wpe.weight": ((cfg["max_position_embeddings"], H),
                               "normal"),
            "gpt.ln_f.weight": ((H,), "ones"),
            "gpt.ln_f.bias": ((H,), "normal")}
    for n, s in shapes.items():     # "*": the layers, stacked in front
        spec[f"gpt.h.*.{n}"] = ((cfg["num_layers"],) + s, kind(n))
    return spec


def block(x, p, cfg, prec):
    B, T, H = x.shape
    nh = cfg["num_heads"]
    hd = H // nh
    eps = cfg["layer_norm_epsilon"]
    h = layer_norm(x, p["ln_1.weight"], p["ln_1.bias"], eps, prec)
    qkv = mm(h, p["attn.qkv_proj.weight"], prec) \
        + p["attn.qkv_proj.bias"].astype(act_dtype(prec))
    q, k, v = jnp.moveaxis(qkv.reshape(B, T, 3, nh, hd), 2, 0)
    s = mm(q, k, prec, "bqhd,bkhd->bhqk").astype(jnp.float32) / (hd ** 0.5)
    mask = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(mask[None, None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1).astype(act_dtype(prec))
    o = mm(a, v, prec, "bhqk,bkhd->bqhd").reshape(B, T, H)
    x = x + mm(o, p["attn.out_proj.weight"], prec) \
        + p["attn.out_proj.bias"].astype(act_dtype(prec))
    h = layer_norm(x, p["ln_2.weight"], p["ln_2.bias"], eps, prec)
    f = mm(h, p["mlp.fc_in.weight"], prec) \
        + p["mlp.fc_in.bias"].astype(act_dtype(prec))
    f = jax.nn.gelu(f, approximate=True)
    return x + mm(f, p["mlp.fc_out.weight"], prec) \
        + p["mlp.fc_out.bias"].astype(act_dtype(prec))


def forward(w, cfg, ids, prec="f32"):
    """Logits [B, T, V] (float32) of token ids [B, T]. Layers run under
    lax.scan with each block rematerialised, so that the backward pass
    of a full-width model fits beside its weights."""
    T = ids.shape[1]
    dt = act_dtype(prec)
    x = (w["gpt.wte.weight"][ids] + w["gpt.wpe.weight"][:T][None]).astype(dt)
    stacked = {n: w[f"gpt.h.*.{n}"] for n in LAYER_LEAVES}
    step = jax.checkpoint(lambda h, p: (block(h, p, cfg, prec), None))
    x, _ = jax.lax.scan(step, x, stacked)
    x = layer_norm(x, w["gpt.ln_f.weight"], w["gpt.ln_f.bias"],
                   cfg["layer_norm_epsilon"], prec)
    return mm(x, w["gpt.wte.weight"].T, prec).astype(jnp.float32)
