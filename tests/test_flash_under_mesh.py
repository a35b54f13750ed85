"""Where the flash kernel is partitioned: by the builder of an
auto-partitioned SPMD program (HybridTrainStep), never by the kernel
looking at the installed fleet mesh. On the chip flash is the default
attention; here it runs in interpret mode (PADDLE_TPU_FLASH=interpret,
set through the module's switch) so that the CPU sees the same trace:

- HybridTrainStep over a fleet mesh runs the kernel per shard (a
  shard_map in its program) and gives the plain composition's loss;
- inside the shard_map of LocalSGD and of PipelineParallel the kernel is
  called bare (a nested shard_map over the same mesh is refused at
  trace);
- a one-chip TrainStep and a bare attention call stay un-partitioned
  however many devices a fleet mesh was installed over.
"""
import re

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.ops as ops
from paddle_tpu import optimizer as opt
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed.env import build_mesh, set_mesh
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny


@pytest.fixture
def flash(flash_interpret, monkeypatch):
    """Flash attention on in interpret mode (conftest's switch); the
    returned function switches it off again (the plain composition)."""
    yield lambda: monkeypatch.setattr(ops, "_FLASH_ENV", "0")
    set_mesh(None)


def _loss(out, y):
    return nn.functional.cross_entropy(
        out.reshape([-1, out.shape[-1]]), y.reshape([-1]))


def _ids(batch=8, seq=128):
    return paddle.to_tensor(
        np.random.RandomState(0).randint(0, 1024, size=(batch, seq)))


def _fleet(**degrees):
    strategy = fleet.DistributedStrategy()
    for k, v in degrees.items():
        strategy.hybrid_configs[f"{k}_degree"] = v
    fleet.init(is_collective=True, strategy=strategy)
    return strategy


def _gpt_step_losses(build, n=2):
    paddle.seed(0)
    m = GPTForCausalLM(gpt_tiny())
    o = opt.SGD(learning_rate=0.05, parameters=m.parameters())
    step = build(m, o)
    ids = _ids()
    return step, [float(step(ids, ids).item()) for _ in range(n)]


class Attn(nn.Layer):
    """x -> x + attention(x): the smallest layer that reaches
    F.scaled_dot_product_attention."""

    def __init__(self, width=64, heads=4):
        super().__init__()
        self.qkv = nn.Linear(width, 3 * width)
        self.heads = heads

    def forward(self, x):
        B, T, W = x.shape
        q, k, v = self.qkv(x).reshape(
            [B, T, 3, self.heads, W // self.heads]).unbind(axis=2)
        a = nn.functional.scaled_dot_product_attention(
            q, k, v, is_causal=True)
        return x + a.reshape([B, T, W])


def test_hybrid_step_runs_the_kernel_per_shard(flash):
    _fleet(dp=2, sharding=2, mp=2)
    build = lambda m, o: fleet.build_train_step(m, _loss, o)
    step, got = _gpt_step_losses(build)
    ids = _ids()
    # the kernel, under the scope that names its layout, per shard
    assert re.search(r"shard_map/flash\.(direct|folded)/flash_attention_fwd",
                     step.compiled_text(ids, ids))
    flash()                                   # the plain composition
    _, want = _gpt_step_losses(build)
    np.testing.assert_allclose(got, want, rtol=2e-4)


def test_localsgd_body_calls_the_kernel_bare(flash):
    s = _fleet(dp=8)
    s.localsgd = True
    s.localsgd_configs["k_steps"] = 2
    s.localsgd_configs["begin_step"] = 0
    fleet.init(is_collective=True, strategy=s)
    build = lambda m, o: fleet.build_train_step(m, _loss, o)
    _, got = _gpt_step_losses(build)          # a local and a sync step
    flash()
    _, want = _gpt_step_losses(build)
    np.testing.assert_allclose(got, want, rtol=2e-4)


def test_pipeline_stage_calls_the_kernel_bare(flash):
    from paddle_tpu.distributed.meta_parallel import (
        LayerDesc, PipelineLayer, PipelineParallel)
    _fleet(dp=8)                   # a fleet mesh is installed AND unused

    def loss_once():
        paddle.seed(0)
        mesh = build_mesh(dp=1, pp=4, mp=1, devices=jax.devices()[:4])
        pipe = PipelineLayer(
            [LayerDesc(Attn) for _ in range(4)], num_stages=4,
            loss_fn=lambda o, y: ((o - y) ** 2).mean())
        o = opt.SGD(learning_rate=0.02, parameters=pipe.parameters())
        eng = PipelineParallel(pipe, o, mesh, n_micro=4)
        rng = np.random.RandomState(0)
        x = paddle.to_tensor(rng.randn(8, 128, 64).astype(np.float32))
        y = paddle.to_tensor(rng.randn(8, 128, 64).astype(np.float32))
        return float(eng.train_batch(x, y).item())

    got = loss_once()
    flash()
    np.testing.assert_allclose(got, loss_once(), rtol=2e-4)


def test_one_chip_paths_ignore_an_installed_mesh(flash):
    """get_mesh()/fleet.init leave a dp=N mesh installed: a one-chip
    TrainStep and a bare attention call made afterwards hold no
    shard_map and touch one device."""
    from paddle_tpu.jit import TrainStep
    _fleet(dp=8)
    x = paddle.to_tensor(
        np.random.RandomState(0).randn(8, 128, 4, 16).astype(np.float32))
    call = lambda a: nn.functional.scaled_dot_product_attention(
        paddle.Tensor(a), paddle.Tensor(a), paddle.Tensor(a),
        is_causal=True).value
    assert "shard_map" not in str(jax.make_jaxpr(call)(x.value))
    step, losses = _gpt_step_losses(
        lambda m, o: TrainStep(m, _loss, o))
    assert np.isfinite(losses).all()
    ids = _ids()
    text = step.compiled_text(ids, ids)
    assert "flash_attention_fwd" in text and "shard_map" not in text
    for leaf in jax.tree.leaves(step.params):
        assert len(leaf.devices()) == 1
