"""The program side of the rehearsal's block-diffusion cell
(tests/data/diffusion_cell.json): a two-layer language model trained by
block diffusion, built from paddle_tpu's public nn pieces as a user would
build it. Not the system under test's own model: it stands in for one,
so that the runner's block-diffusion path (lib/train.py) is driven end
to end on the CPU before any configuration needs it.

forward(noised, clean, weights) runs the 2S positions of
[noised ; clean] once, under the BD3-LM mask (arXiv:2503.09573) given to
F.scaled_dot_product_attention as a dense boolean attn_mask, and returns
sum_i weights[i] * CE(logits at noised position i, clean[i]) / (B S),
float32: the objective's contract. Both copies take positions 0..S-1.
Each layer is pre-RMSNorm: x + o(attn(q, k, v)), then x + mlp(x), the
feed-forward SiLU-gated; untied head."""
import dataclasses

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F


@dataclasses.dataclass
class BlockDiffusionConfig:
    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    intermediate_size: int
    max_position_embeddings: int
    block_length: int
    rms_norm_eps: float


def bd3lm_mask(seq, block_length):
    """[2 seq, 2 seq] bool, query by key, over [noised ; clean]: a noised
    block sees its own noised block and the clean blocks before it; a
    clean block sees the clean blocks up to and including itself."""
    pos = np.arange(2 * seq)
    blk, noised = pos % seq // block_length, pos < seq
    bq, bk = blk[:, None], blk[None, :]
    return np.where(noised[:, None],
                    np.where(noised[None, :], bq == bk, bk < bq),
                    ~noised[None, :] & (bk <= bq))


class Block(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        H = cfg.hidden_size
        self.heads = cfg.num_attention_heads
        self.input_layernorm = nn.RMSNorm(H, cfg.rms_norm_eps)
        self.q_proj = nn.Linear(H, H, bias_attr=False)
        self.k_proj = nn.Linear(H, H, bias_attr=False)
        self.v_proj = nn.Linear(H, H, bias_attr=False)
        self.o_proj = nn.Linear(H, H, bias_attr=False)
        self.post_attention_layernorm = nn.RMSNorm(H, cfg.rms_norm_eps)
        self.mlp = nn.GatedMLP(H, cfg.intermediate_size)

    def forward(self, x, mask):
        B, T, H = x.shape
        h = self.input_layernorm(x)
        q, k, v = (p(h).reshape([B, T, self.heads, H // self.heads])
                   for p in (self.q_proj, self.k_proj, self.v_proj))
        a = F.scaled_dot_product_attention(q, k, v, attn_mask=mask)
        x = x + self.o_proj(a.reshape([B, T, H]))
        return x + self.mlp(self.post_attention_layernorm(x))


class BlockDiffusionLM(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        H, V = cfg.hidden_size, cfg.vocab_size
        self.embed_tokens = nn.Embedding(V, H)
        self.embed_positions = nn.Embedding(cfg.max_position_embeddings, H)
        self.layers = nn.LayerList(
            [Block(cfg) for _ in range(cfg.num_hidden_layers)])
        self.norm = nn.RMSNorm(H, cfg.rms_norm_eps)
        self.lm_head = nn.Linear(H, V, bias_attr=False)

    def forward(self, noised, clean, weights):
        B, S = noised.shape
        pos = paddle.to_tensor(np.concatenate([np.arange(S)] * 2))
        x = self.embed_tokens(paddle.concat([noised, clean], axis=1)) \
            + self.embed_positions(pos)
        mask = paddle.to_tensor(bd3lm_mask(S, self.cfg.block_length))
        for layer in self.layers:
            x = layer(x, mask)
        logits = self.lm_head(self.norm(x[:, :S]))
        ce = F.cross_entropy(logits.reshape([B * S, -1]),
                             clean.reshape([B * S]), reduction="none")
        return (ce * weights.reshape([B * S])).sum() / (B * S)
