"""Required operations and bytes of the GLM-4.x / DeepSeek-V3 decoder
(latent attention, sigmoid-routed experts) as ONE chip's share of an
expert-parallel group runs it, from the configuration's own keys — the
numerators of `step_mfu_moe.train` and `mla_flash_roofline.train`. As in
work.py: what the mathematics needs, never what an implementation does
(recomputation and the worst-case buffer are the program's choices).

Of the routed experts only the assignments to experts HELD here are this
chip's work, so their count is an argument: the program counts it
(`moe.local_assignments`), and 4 x 8/64 a token is its expectation under
uniform routing."""
from . import work


def mla_matmul_params(cfg):
    H, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rq, rkv, v = cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["v_head_dim"]
    return (H * rq + rq * nh * (nope + rope) + H * (rkv + rope)
            + rkv * nh * (nope + v) + nh * v * H)


def expert_params(cfg):
    """One gated feed-forward of the experts' width: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def fixed_matmul_params(cfg):
    """Weights EVERY token is multiplied by: attention in every layer,
    the dense layers' feed-forward, the shared experts and the router in
    every expert layer, the untied head. The embedding is a look-up."""
    H = cfg["hidden_size"]
    n_dense = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - n_dense
    return (cfg["num_hidden_layers"] * mla_matmul_params(cfg)
            + n_dense * 3 * H * cfg["intermediate_size"]
            + n_moe * (cfg["n_shared_experts"] * expert_params(cfg)
                       + H * cfg["router_experts"])
            + H * cfg["vocab_size"])


def attention_forward_flops_per_token(cfg, seq, causal=True):
    """QK^T at the query/key head dim and PV at the value head dim, for
    one token of a sequence of `seq`, one layer."""
    keys = (seq + 1) / 2.0 if causal else float(seq)
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return 2.0 * cfg["num_attention_heads"] * (qk + cfg["v_head_dim"]) * keys


def expected_local_assignments(cfg, tokens):
    """Under uniform routing: top-k x held / routed a token and layer."""
    n_moe = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    return (tokens * n_moe * cfg["num_experts_per_tok"]
            * cfg["n_routed_experts"] / cfg["router_experts"])


def train_flops(cfg, seq, tokens, local_assignments):
    """Forward + backward of `tokens` tokens in sequences of `seq`: 6 per
    matmul weight a token meets, 3 x the attention forward, and 6 per
    weight of a held expert for each assignment to one (summed over the
    expert layers)."""
    return (tokens * (6.0 * fixed_matmul_params(cfg)
                      + 3.0 * cfg["num_hidden_layers"]
                      * attention_forward_flops_per_token(cfg, seq))
            + 6.0 * expert_params(cfg) * local_assignments)


def mla_flash_work(cfg, batch, seq, itemsize=2):
    """work.flash_attention_work at this configuration's own head dim
    (query/key nope + rope, which the value head must equal)."""
    d = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    if cfg["v_head_dim"] != d:
        raise ValueError("value head dim differs from the query/key's")
    return work.flash_attention_work(batch, cfg["num_attention_heads"], seq,
                                     d, itemsize)
