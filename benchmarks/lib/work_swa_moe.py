"""Required operations and bytes of the SmallThinker decoder (grouped-
query attention, full and windowed layers mixed, softmax-routed
ReLU-gated experts) as ONE chip's share of an expert-parallel group runs
it, from the configuration's own keys — the numerators of
`step_mfu_swa_moe.train` and `gqa_window_flash_roofline.train`. As in
work.py: what the mathematics needs, never what an implementation does
(recomputation, the worst-case buffer, tiles the mask leaves empty and a
key/value head read once per query head are the program's choices).

Attention is counted causally AND inside the band: a query of a window
layer sees at most `sliding_window_size` keys. Of the routed experts only
the assignments to experts HELD here are this chip's work, so their count
is an argument: the program counts it (`moe.local_assignments`), and 6 x
8/64 a token and layer is its expectation under uniform routing."""


def windows(cfg):
    """The window of each layer built (None: full causal attention)."""
    n = cfg["num_hidden_layers"]
    return [cfg["sliding_window_size"] if s else None
            for s in cfg["sliding_window_layout"][:n]]


def visible_pairs(seq, window=None):
    """(query, key) pairs with key <= query and query - key < window in
    one sequence of `seq`."""
    if window is None or window >= seq:
        return seq * (seq + 1) / 2.0
    return window * (window + 1) / 2.0 + (seq - window) * float(window)


def attention_matmul_params(cfg):
    H, hd = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 2 * H * nh * hd + 2 * H * nkv * hd


def expert_params(cfg):
    """One gated feed-forward of the experts' width: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_ffn_hidden_size"]


def fixed_matmul_params(cfg):
    """Weights EVERY token is multiplied by: attention and the router in
    every layer, the untied head. The embedding is a look-up."""
    H = cfg["hidden_size"]
    return (cfg["num_hidden_layers"] * (attention_matmul_params(cfg)
                                        + H * cfg["router_experts"])
            + H * cfg["vocab_size"])


def attention_forward_flops_per_token(cfg, seq):
    """QK^T and PV over the visible keys, summed over the layers, for
    the mean token of a sequence of `seq`."""
    pairs = sum(visible_pairs(seq, w) for w in windows(cfg))
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * pairs / seq


def expected_local_assignments(cfg, tokens):
    """Under uniform routing: top-k x held / routed a token and layer."""
    return (tokens * cfg["num_hidden_layers"]
            * cfg["moe_num_active_primary_experts"]
            * cfg["moe_num_primary_experts"] / cfg["router_experts"])


def train_flops(cfg, seq, tokens, local_assignments):
    """Forward + backward of `tokens` tokens in sequences of `seq`: 6 per
    matmul weight a token meets, 3 x the attention forward, and 6 per
    weight of a held expert for each assignment to one (summed over the
    layers)."""
    return (tokens * (6.0 * fixed_matmul_params(cfg)
                      + 3.0 * attention_forward_flops_per_token(cfg, seq))
            + 6.0 * expert_params(cfg) * local_assignments)


def flash_work(cfg, batch, seq, itemsize=2):
    """FLOPs and HBM bytes of the three flash kernels over ALL layers of
    one step, as work.flash_attention_work counts them (forward 2
    matmuls over the visible pairs, dq 3, dkv 4; each operand read or
    written once), with k, v, dk, dv at the key/value heads' count."""
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    pairs = sum(visible_pairs(seq, w) for w in windows(cfg))
    mm = 2.0 * batch * nh * pairs * hd              # one matmul, all layers
    layers = cfg["num_hidden_layers"]
    q_like = layers * batch * nh * seq * hd * itemsize
    kv_like = layers * batch * nkv * seq * hd * itemsize
    return {
        "flash_attention_fwd": {"flops": 2 * mm,
                                "bytes": 2 * q_like + 2 * kv_like},
        "flash_attention_dq": {"flops": 3 * mm,
                               "bytes": 4 * q_like + 2 * kv_like},
        "flash_attention_dkv": {"flops": 4 * mm,
                                "bytes": 2 * q_like + 4 * kv_like},
    }
