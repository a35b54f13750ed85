"""Plain reference of the selective-state-space stack AS THE REPO BUILDS
IT (models/ssm.py, attn_every=0): token embedding, pre-LayerNorm
residual blocks around one Mamba-1 mixer each, final LayerNorm, output
head tied to the embedding. The mixer follows Gu & Dao (2023), section
3: in-projection to (x, z), causal depthwise convolution of d_conv taps
and SiLU, input-dependent (dt, B, C), the recurrence
    h_t = exp(dt_t A) h_{t-1} + (dt_t x_t) B_t ,   y_t = C_t . h_t + D x_t
run one token at a time under lax.scan, gating by SiLU(z), and the
out-projection. Imports nothing of the program.

Departures from the published state-spaces/mamba-1.4b, which are the
program's and are stated in the configuration file: LayerNorm with bias
where the checkpoint has RMSNorm; an out-projection bias."""
import jax
import jax.numpy as jnp

from .common import act_dtype, layer_norm, mm

LAYER_LEAVES = ("ln_1.weight", "ln_1.bias", "mixer.in_proj.weight",
                "mixer.conv_weight", "mixer.conv_bias",
                "mixer.x_proj.weight", "mixer.dt_proj.weight",
                "mixer.dt_proj.bias", "mixer.A_log", "mixer.D",
                "mixer.out_proj.weight", "mixer.out_proj.bias")


def param_spec(cfg):
    H, V = cfg["hidden_size"], cfg["vocab_size"]
    d = cfg["expand"] * H
    N, K, R = cfg["d_state"], cfg["d_conv"], cfg["dt_rank"]
    # Served random weights must not be degenerate. With the head tied to
    # the embedding and unit norm gains, LN(x).e_v is largest for the
    # token just read (x holds its embedding) and greedy decoding repeats
    # it by a margin of several units, at any precision; a constant bias
    # summed over 48 layers does the same for one fixed token. So norm
    # gains are random signs (the self-term sum g_i e_i^2 is then
    # zero-mean) and biases are zero but dt's. The convolution's taps
    # and dt's bias follow the published init (taps of order 1/sqrt(K),
    # steps of 1e-3..1e-1), so that the recurrent state carries a long
    # history at a size that matters beside the skip path: with 0.02
    # taps the stack is all but memoryless and greedy decoding cycles
    # through two or three tokens.
    leaves = {"ln_1.weight": ((H,), "sign"), "ln_1.bias": ((H,), "zeros"),
              "mixer.in_proj.weight": ((H, 2 * d), "normal"),
              "mixer.conv_weight": ((K, d), "normal:0.5"),
              "mixer.conv_bias": ((d,), "zeros"),
              "mixer.x_proj.weight": ((d, R + 2 * N), "normal"),
              "mixer.dt_proj.weight": ((R, d), "normal"),
              "mixer.dt_proj.bias": ((d,), "dt_bias"),
              "mixer.A_log": ((d, N), "a_log"), "mixer.D": ((d,), "ones"),
              "mixer.out_proj.weight": ((d, H), "normal"),
              "mixer.out_proj.bias": ((H,), "zeros")}
    spec = {"ssm.wte.weight": ((V, H), "normal"),
            "ssm.ln_f.weight": ((H,), "sign"),
            "ssm.ln_f.bias": ((H,), "zeros")}
    for n, (shape, kind) in leaves.items():   # "*": layers, stacked
        spec[f"ssm.h.*.{n}"] = ((cfg["num_layers"],) + shape, kind)
    return spec


def mixer(h, p, cfg, prec):
    B, T, _ = h.shape
    N, K, R = cfg["d_state"], cfg["d_conv"], cfg["dt_rank"]
    dt_act = act_dtype(prec)
    xz = mm(h, p["mixer.in_proj.weight"], prec)
    d = xz.shape[-1] // 2
    xin, z = xz[..., :d], xz[..., d:]
    w = p["mixer.conv_weight"].astype(jnp.float32)
    acc = jnp.zeros((B, T, d), jnp.float32)
    for s in range(K):             # tap s looks s tokens back
        prev = jnp.pad(xin.astype(jnp.float32),
                       ((0, 0), (s, 0), (0, 0)))[:, :T]
        acc = acc + prev * w[K - 1 - s]
    xc = jax.nn.silu(acc + p["mixer.conv_bias"].astype(jnp.float32)
                     ).astype(dt_act)
    dbc = mm(xc, p["mixer.x_proj.weight"], prec)
    dt = jax.nn.softplus(
        (mm(dbc[..., :R], p["mixer.dt_proj.weight"], prec)
         + p["mixer.dt_proj.bias"].astype(dt_act)).astype(jnp.float32))
    b_t = dbc[..., R:R + N].astype(jnp.float32)
    c_t = dbc[..., R + N:].astype(jnp.float32)
    A = -jnp.exp(p["mixer.A_log"].astype(jnp.float32))          # [d, N]
    x32 = xc.astype(jnp.float32)

    def step(hs, inp):             # hs [B, d, N]; the state is float32
        x_t, dt_t, bt, ct = inp
        hs = jnp.exp(dt_t[..., None] * A) * hs \
            + (dt_t * x_t)[..., None] * bt[:, None, :]
        return hs, (hs * ct[:, None, :]).sum(-1)

    seq = tuple(jnp.moveaxis(a, 1, 0) for a in (x32, dt, b_t, c_t))
    _, ys = jax.lax.scan(step, jnp.zeros((B, d, N), jnp.float32), seq)
    y = jnp.moveaxis(ys, 0, 1) + x32 * p["mixer.D"].astype(jnp.float32)
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(dt_act)
    return mm(y, p["mixer.out_proj.weight"], prec) \
        + p["mixer.out_proj.bias"].astype(dt_act)


def forward(w, cfg, ids, prec="f32"):
    """Logits [B, T, V] (float32) of token ids [B, T]; layers one after
    another in a Python loop over a jitted block would compile once per
    layer, so they run under lax.scan over the stacked leaves."""
    dt = act_dtype(prec)
    x = w["ssm.wte.weight"][ids].astype(dt)
    stacked = {n: w[f"ssm.h.*.{n}"] for n in LAYER_LEAVES}
    eps = cfg["layer_norm_epsilon"]

    def layer(h, p):
        n = layer_norm(h, p["ln_1.weight"], p["ln_1.bias"], eps, prec)
        return h + mixer(n, p, cfg, prec), None

    x, _ = jax.lax.scan(layer, x, stacked)
    x = layer_norm(x, w["ssm.ln_f.weight"], w["ssm.ln_f.bias"], eps, prec)
    return mm(x, w["ssm.wte.weight"].T, prec).astype(jnp.float32)
