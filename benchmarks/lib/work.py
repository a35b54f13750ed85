"""The operations and bytes an algorithm REQUIRES, from shapes alone.

These are the yardstick's numerators: a share of a peak or of a roofline
divides what is computed here by a time the benchmark measured. They
count what the mathematics needs, never what an implementation does —
recomputation under rematerialisation, padding to a tile, a second pass
over attention on a sharded axis are all work the program chose, so they
lower the share instead of raising the count.

Model steps take the configuration's dict (the cell's config file);
kernels are keyed by the stable kernel name the trace shows."""


# ---------------------------------------------------------------- GPT
def gpt_matmul_params(cfg):
    """Weights that a token is multiplied by: per layer qkv (3H^2), out
    (H^2), the two FFN matrices (2 H F), and the tied output head (V H).
    Embedding look-ups, biases and norms are not matmuls."""
    H, F = cfg["hidden_size"], cfg["intermediate_size"]
    return cfg["num_layers"] * (4 * H * H + 2 * H * F) \
        + cfg["vocab_size"] * H


def attention_flops_per_token(n_layers, hidden, context, causal=True):
    """Forward FLOPs of QK^T and PV for ONE token attending `context`
    keys: 2 matmuls x 2 FLOPs x hidden per key. `causal` takes the mean
    over a sequence of `context` tokens, where token i sees i+1 keys."""
    keys = (context + 1) / 2.0 if causal else context
    return n_layers * 4.0 * hidden * keys


def gpt_train_flops_per_token(cfg, seq, causal=True):
    """Forward + backward (backward = 2 x forward): 6 FLOPs per matmul
    weight and 3 x the attention forward."""
    return 6.0 * gpt_matmul_params(cfg) + 3.0 * attention_flops_per_token(
        cfg["num_layers"], cfg["hidden_size"], seq, causal)


def gpt_forward_flops_token(cfg, context):
    """Forward FLOPs of one served token whose attention sees `context`
    keys (its own included): the matmul weights twice, plus attention."""
    return 2.0 * gpt_matmul_params(cfg) + cfg["num_layers"] * 4.0 \
        * cfg["hidden_size"] * context


# -------------------------------------------------------------- Mamba
def mamba_dims(cfg):
    d = cfg["expand"] * cfg["hidden_size"]
    return d, cfg["d_state"], cfg["d_conv"], cfg["dt_rank"]


def mamba_matmul_params(cfg):
    H = cfg["hidden_size"]
    d, N, _, R = mamba_dims(cfg)
    per_layer = H * 2 * d + d * (R + 2 * N) + R * d + d * H
    return cfg["num_layers"] * per_layer + cfg["vocab_size"] * H


def ssm_scan_flops_per_token(d_inner, d_state):
    """h = exp(dt A) h + (dt B) x ; y = C.h, per channel and state: the
    decay dt*A (1), the input dt*x*B (2), multiply-add into h (2),
    C.h multiply-add (2): 7 per (channel, state); exp is not a FLOP."""
    return 7.0 * d_inner * d_state


def ssm_scan_bytes(n_tokens, n_rows, d_inner, d_state, itemsize=4):
    """Unpadded traffic of one scan call: x, dt, y [T, d]; B, C [T, N];
    A [d, N]; the state [rows, d, N] read once and written once."""
    return itemsize * (3 * n_tokens * d_inner + 2 * n_tokens * d_state
                       + d_inner * d_state + 2 * n_rows * d_inner * d_state)


def mamba_forward_flops_token(cfg):
    d, N, K, _ = mamba_dims(cfg)
    per_layer = ssm_scan_flops_per_token(d, N) + 2.0 * K * d
    return 2.0 * mamba_matmul_params(cfg) + cfg["num_layers"] * per_layer


# ------------------------------------------------------------ kernels
def flash_attention_work(batch, heads, seq, head_dim, itemsize=2,
                         causal=True):
    """FLOPs and HBM bytes of flash attention's three kernels over ONE
    layer's [batch, heads, seq, head_dim] call. Forward: QK^T and PV.
    Backward: dq needs S and dP recomputed plus dQ (3 matmuls), dkv
    needs S, dP, dV and dK (4 matmuls) — these recomputations are the
    flash ALGORITHM's, so they are required work of that kernel. Bytes:
    each operand read or written once."""
    pairs = seq * (seq + 1) / 2.0 if causal else float(seq * seq)
    mm = 2.0 * batch * heads * pairs * head_dim        # one matmul
    tensor = batch * heads * seq * head_dim * itemsize
    return {
        "flash_attention_fwd": {"flops": 2 * mm, "bytes": 4 * tensor},
        "flash_attention_dq": {"flops": 3 * mm, "bytes": 6 * tensor},
        "flash_attention_dkv": {"flops": 4 * mm, "bytes": 7 * tensor},
    }


def ragged_rows_work(rows, heads, head_dim, page_size, itemsize=2):
    """`rows`: (tokens_in_row, context_after_row) per row of one step.
    A row of n tokens ending at context c: token j sees c - n + 1 + j
    keys; the row reads its ceil(c / page) pages of K and V once."""
    flops = kv_pages = n_tok = 0.0
    for n, c in rows:
        flops += 4.0 * heads * head_dim * (n * (c - n) + n * (n + 1) / 2.0)
        kv_pages += -(-c // page_size)
        n_tok += n
    kv = 2.0 * kv_pages * page_size * heads * head_dim * itemsize
    qo = 2.0 * n_tok * heads * head_dim * itemsize
    return {"flops": flops, "bytes": kv + qo}


def roofline_seconds(flops, nbytes, peak):
    """The least time the chip could take, and which roof sets it."""
    t_c = flops / peak["bf16_flops"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
