"""nn.RMSNorm, nn.RotaryEmbedding, nn.GatedMLP and the decoder stack's
assembly from per-layer specs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.models.decoder import (DecoderConfig, DecoderForCausalLM,
                                       glm_4_7_flash_ep8)


def test_rms_norm_statistics_are_float32():
    x = np.random.RandomState(0).randn(3, 5, 16).astype(np.float32) * 7
    layer = nn.RMSNorm(16, epsilon=1e-5)
    g = np.linspace(0.5, 1.5, 16, dtype=np.float32)
    layer.weight.set_value(g)
    want = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * g
    np.testing.assert_allclose(layer(paddle.to_tensor(x)).numpy(), want,
                               rtol=1e-5)
    assert [n for n, _ in layer.named_parameters()] == ["weight"]
    # bf16 in, bf16 out, the mean of squares taken in float32
    xb = paddle.to_tensor(x).astype("bfloat16")
    out = F.rms_norm(xb, layer.weight)
    assert out.dtype == paddle.bfloat16
    np.testing.assert_allclose(out.astype("float32").numpy(), want,
                               rtol=0.02, atol=0.02)


def test_rms_norm_gradient():
    x = paddle.to_tensor(np.random.RandomState(1).randn(4, 8).astype(
        np.float32), stop_gradient=False)
    layer = nn.RMSNorm(8)
    (layer(x) ** 2).sum().backward()
    assert x.grad is not None and layer.weight.grad is not None
    # the norm is scale-free: x . dL/dx = 0 up to epsilon
    assert abs(float((x * x.grad).sum().numpy())) < 1e-3


def test_rotary_keeps_norms_and_encodes_relative_position():
    rng = np.random.RandomState(2)
    rot = nn.RotaryEmbedding(16, theta=100.0)
    q = rng.randn(1, 12, 2, 16).astype(np.float32)
    rq = rot(paddle.to_tensor(q)).numpy()
    np.testing.assert_allclose(np.linalg.norm(rq, axis=-1),
                               np.linalg.norm(q, axis=-1), rtol=1e-5)
    np.testing.assert_allclose(rq[:, 0], q[:, 0], atol=1e-6)  # position 0
    # the same two vectors at positions (m, n) and (m + 3, n + 3)
    a, b = rng.randn(16).astype(np.float32), rng.randn(16).astype(np.float32)
    seq = np.zeros((1, 12, 1, 16), np.float32)
    seq[0, 2, 0], seq[0, 5, 0] = a, a
    seq2 = np.zeros_like(seq)
    seq2[0, 4, 0], seq2[0, 7, 0] = b, b
    ra, rb = rot(paddle.to_tensor(seq)).numpy(), \
        rot(paddle.to_tensor(seq2)).numpy()
    np.testing.assert_allclose(ra[0, 2, 0] @ rb[0, 4, 0],
                               ra[0, 5, 0] @ rb[0, 7, 0], rtol=1e-4)
    # explicit positions: the default is 0 .. seq - 1
    pos = paddle.to_tensor(np.arange(12, dtype=np.int32))
    np.testing.assert_allclose(rot(paddle.to_tensor(q), pos).numpy(), rq,
                               atol=1e-6)
    with pytest.raises(ValueError):
        rot(paddle.to_tensor(q[..., :8]))
    with pytest.raises(ValueError):
        nn.RotaryEmbedding(7)


def test_gated_mlp():
    mlp = nn.GatedMLP(8, 24)
    x = np.random.RandomState(3).randn(5, 8).astype(np.float32)
    g, u, d = (p.numpy() for p in (mlp.gate_proj.weight, mlp.up_proj.weight,
                                   mlp.down_proj.weight))
    h = x @ g
    want = (h / (1 + np.exp(-h)) * (x @ u)) @ d
    np.testing.assert_allclose(mlp(paddle.to_tensor(x)).numpy(), want,
                               rtol=1e-4, atol=1e-5)
    assert sorted(n for n, _ in mlp.named_parameters()) == [
        "down_proj.weight", "gate_proj.weight", "up_proj.weight"]


def test_stack_is_assembled_from_its_layer_specs():
    cfg = DecoderConfig(num_hidden_layers=4, n_routed_experts=4,
                        first_k_dense_replace=1, n_shared_experts=1)
    assert cfg.layers == [("mla", "dense")] + [("mla", "moe")] * 3
    model = DecoderForCausalLM(cfg)
    assert len(model.model.lead) == 1 and len(model.model.h) == 3
    names = [n for n, _ in model.named_parameters()]
    assert "model.lead.0.mlp.down_proj.weight" in names
    assert "model.h.2.mlp.experts_down" in names
    assert "model.h.0.mlp.shared.up_proj.weight" in names
    assert not any("bias" in n for n in names)
    # the selection bias is a buffer, not a parameter
    assert "model.h.0.mlp.e_score_correction_bias" in dict(
        model.named_buffers())
    # an explicit spec list: dense layers only, all uniform
    dense = DecoderForCausalLM(DecoderConfig(
        num_hidden_layers=2, layers=[("mla", "dense")] * 2))
    assert len(dense.model.lead) == 0 and len(dense.model.h) == 2
    with pytest.raises(ValueError):
        DecoderConfig(num_hidden_layers=3, layers=[("mla", "dense")])
    with pytest.raises(KeyError):
        DecoderForCausalLM(DecoderConfig(num_hidden_layers=1,
                                         layers=[("conv", "dense")]))


@pytest.mark.parametrize("n_layers, scanned", [(2, False), (3, True)])
def test_traced_stack_matches_eager(n_layers, scanned):
    """Under a trace the uniform run is one scan (two expert layers) or a
    single checkpointed call (one); eager is a plain loop over layers."""
    kw = dict(vocab_size=64, hidden_size=32, intermediate_size=48,
              num_hidden_layers=n_layers, num_attention_heads=2,
              q_lora_rank=16, kv_lora_rank=8, qk_nope_head_dim=12,
              qk_rope_head_dim=4, v_head_dim=16, moe_intermediate_size=24,
              n_routed_experts=4, first_k_dense_replace=1,
              n_shared_experts=1)
    paddle.seed(0)
    model = DecoderForCausalLM(DecoderConfig(**kw))
    assert (len(model.model.h) > 1) == scanned
    ids = paddle.to_tensor(np.random.RandomState(0).randint(0, 64, (2, 16)))
    eager = model(ids).numpy()
    # the eager forward leaves the routing load on the stack
    assert model.model.step_counter_names[0] == "moe.assignments"
    assert int(model.model._step_counters[0]) == 2 * 16 * 2 * (n_layers - 1)
    from paddle_tpu.jit.api import functional_call, state_arrays
    params, buffers = state_arrays(model)
    fn = jax.jit(lambda p: functional_call(model, p, buffers, (ids.value,)))
    np.testing.assert_allclose(fn(params), eager, atol=2e-5)


def test_preset_holds_the_published_widths():
    cfg = glm_4_7_flash_ep8()
    assert (cfg.hidden_size, cfg.intermediate_size,
            cfg.moe_intermediate_size) == (2048, 10240, 1536)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim,
            cfg.num_attention_heads) == (768, 512, 192, 64, 256, 20)
    assert (cfg.router_experts, cfg.n_routed_experts,
            cfg.num_experts_per_tok, cfg.routed_scaling_factor,
            cfg.n_shared_experts) == (64, 8, 4, 1.8, 1)
    assert cfg.layers == [("mla", "dense")] + [("mla", "moe")] * 4


def test_eager_backward_reaches_every_parameter():
    """Outside a trace the stack stays on Tensors, so the eager tape sees
    the embedding, both kinds of layer and the head."""
    paddle.seed(1)
    model = DecoderForCausalLM(DecoderConfig(
        vocab_size=32, hidden_size=16, intermediate_size=24,
        num_hidden_layers=2, num_attention_heads=2, q_lora_rank=8,
        kv_lora_rank=8, qk_nope_head_dim=6, qk_rope_head_dim=2,
        v_head_dim=8, moe_intermediate_size=12, n_routed_experts=4,
        first_k_dense_replace=1, n_shared_experts=1))
    ids = paddle.to_tensor(np.arange(16).reshape(2, 8) % 32)
    model.loss(ids, ids).backward()
    missing = [n for n, p in model.named_parameters() if p.grad is None]
    assert missing == []


def _flash_stack(n_layers):
    """A dense leading layer, then expert layers, at a width and length
    the flash kernels take (head dim 32, value heads as wide)."""
    from paddle_tpu.jit.api import functional_call, state_arrays
    paddle.seed(0)
    model = DecoderForCausalLM(DecoderConfig(
        vocab_size=64, hidden_size=64, intermediate_size=96,
        num_hidden_layers=n_layers, num_attention_heads=2, q_lora_rank=32,
        kv_lora_rank=16, qk_nope_head_dim=24, qk_rope_head_dim=8,
        v_head_dim=32, moe_intermediate_size=48, n_routed_experts=4,
        first_k_dense_replace=1, n_shared_experts=1))
    params, buffers = state_arrays(model)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, 128)),
                      jnp.int32)

    def loss(p):
        logits = functional_call(model, p, buffers, (ids,))
        return jnp.mean(jax.nn.logsumexp(logits.astype(jnp.float32), -1))

    return model, params, loss


@pytest.mark.parametrize("n_layers, bodies", [(2, 2), (4, 2)],
                         ids=["lead_and_one", "lead_and_scan"])
def test_backward_runs_the_flash_forward_once_a_layer(
        flash_interpret, flash_kernel_calls, n_layers, bodies):
    """Every layer is rematerialised from its input AND the flash
    kernel's named out and lse: one forward kernel for each dq / dkv
    pair in the gradient (each traced body counts once: the leading
    layer, and the uniform run as a single call or one scan)."""
    _, params, loss = _flash_stack(n_layers)
    assert flash_kernel_calls(jax.grad(loss), params) == (bodies,) * 3


def test_flash_forward_runs_twice_without_the_policy(
        flash_interpret, flash_kernel_calls, monkeypatch):
    """What the names buy: the same stack under a policy-free
    jax.checkpoint runs the forward kernel again in the backward pass."""
    from paddle_tpu.models import decoder
    monkeypatch.setattr(decoder, "_REMAT_POLICY", None)
    _, params, loss = _flash_stack(4)
    assert flash_kernel_calls(jax.grad(loss), params) == (4, 2, 2)


@pytest.mark.parametrize("n_layers", [2, 4], ids=["lead_and_one",
                                                  "lead_and_scan"])
def test_grads_equal_the_stack_without_remat_bit_for_bit(
        flash_interpret, monkeypatch, n_layers):
    """The backward kernels get the very out and lse the forward made."""
    _, params, loss = _flash_stack(n_layers)
    saved = jax.jit(jax.grad(loss))(params)
    monkeypatch.setattr(jax, "checkpoint", lambda fn, **kw: fn)
    plain = jax.jit(jax.grad(loss))(params)
    for k in plain:
        np.testing.assert_array_equal(np.asarray(saved[k]),
                                      np.asarray(plain[k]), err_msg=k)


def test_one_layer_saves_the_flash_residuals_and_nothing_else(
        flash_interpret, remat_saved):
    """Beside the flash kernel's out and lse, the dense latent layer keeps
    only what its parts tag (tests/test_decoder_remat.py has each kind):
    the two latents (32; 16 + 8 wide), the residual after the mixer, and
    the feed-forward's up and gate (96 wide), the gate through its
    jitted activation."""
    from paddle_tpu.framework.core import Tensor
    from paddle_tpu.models import decoder
    model, _, _ = _flash_stack(2)
    layer = model.model.lead[0]
    fn = jax.checkpoint(lambda h: layer(Tensor(h)).value,
                        policy=decoder._REMAT_POLICY)
    saved = remat_saved(fn, jnp.ones((2, 128, 64), jnp.float32))
    flash = [(aval, why) for aval, why in saved
             if "flash_attention.py" in why]
    assert [aval for aval, _ in flash] == ["float32[2,128,64]",
                                           "float32[4,1,128]"]
    assert "named 'flash_lse'" in flash[1][1]
    rest = [(aval, why) for aval, why in saved if (aval, why) not in flash]
    assert sorted(aval for aval, _ in rest) == [
        "float32[2,128,24]", "float32[2,128,32]", "float32[2,128,64]",
        "float32[2,128,96]", "float32[2,128,96]"]
    assert all("(save_point)" in why or "jitted function 'silu'" in why
               for _, why in rest), rest
