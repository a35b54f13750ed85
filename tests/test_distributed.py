"""Distributed tests on the 8-virtual-device CPU mesh (SURVEY.md §4)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import optimizer as opt
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed.env import build_mesh
from paddle_tpu.distributed.meta_parallel import (PipelineLayer,
                                                  PipelineParallel,
                                                  LayerDesc)
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny

pytestmark = pytest.mark.heavy  # slow-compiling: tier-1 yes, quick commit gate no


def make_loss_fn():
    def loss_fn(out, y):
        return nn.functional.cross_entropy(
            out.reshape([-1, out.shape[-1]]), y.reshape([-1]))
    return loss_fn


class TestMesh:
    def test_build_mesh_axes(self):
        mesh = build_mesh(dp=2, mp=2, sharding=2)
        assert dict(mesh.shape) == {"dp": 2, "sharding": 2, "pp": 1,
                                    "mp": 2, "sp": 1, "ep": 1}

    def test_fleet_init_topology(self):
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs["dp_degree"] = 4
        strategy.hybrid_configs["mp_degree"] = 2
        fleet.init(is_collective=True, strategy=strategy)
        hcg = fleet.get_hybrid_communicate_group()
        assert hcg.get_data_parallel_world_size() == 4
        assert hcg.get_model_parallel_world_size() == 2


class TestHybridTrain:
    @pytest.mark.heavy
    def test_dp_mp_sharding_step(self):
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs["dp_degree"] = 2
        strategy.hybrid_configs["mp_degree"] = 2
        strategy.hybrid_configs["sharding_degree"] = 2
        fleet.init(is_collective=True, strategy=strategy)
        paddle.seed(0)
        m = GPTForCausalLM(gpt_tiny())
        o = opt.AdamW(learning_rate=1e-3, parameters=m.parameters())
        step = fleet.build_train_step(m, make_loss_fn(), o)
        ids = paddle.to_tensor(
            np.random.RandomState(0).randint(0, 1024, size=(8, 16)))
        l0 = step(ids, ids).item()
        for _ in range(3):
            l = step(ids, ids).item()
        assert l < l0
        pk = "gpt.h.0.attn.qkv_proj.weight"
        assert "mp" in str(step.params[pk].sharding.spec)
        assert "sharding" in str(step.opt_state[pk][0].sharding.spec)

    def test_collectives_in_hlo(self):
        """The compiled hybrid step must contain real cross-device
        collectives (dp grad psum / mp activity)."""
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs["dp_degree"] = 4
        strategy.hybrid_configs["mp_degree"] = 2
        fleet.init(is_collective=True, strategy=strategy)
        paddle.seed(0)
        m = GPTForCausalLM(gpt_tiny())
        o = opt.SGD(learning_rate=1e-3, parameters=m.parameters())
        step = fleet.build_train_step(m, make_loss_fn(), o)
        ids = paddle.to_tensor(
            np.random.RandomState(0).randint(0, 1024, size=(8, 16)))
        hlo = step.compiled_text(ids, ids)
        assert "all-reduce" in hlo or "all-gather" in hlo or \
            "reduce-scatter" in hlo

    @pytest.mark.heavy
    def test_dp_matches_single_device(self):
        """dp=8 training must produce the same loss trajectory as a
        single-device run on the same global batch."""
        paddle.seed(0)
        m1 = GPTForCausalLM(gpt_tiny())
        sd = m1.state_dict()

        ids = paddle.to_tensor(
            np.random.RandomState(1).randint(0, 1024, size=(8, 16)))
        from paddle_tpu.jit import TrainStep

        o1 = opt.SGD(learning_rate=0.01, parameters=m1.parameters())
        s1 = TrainStep(m1, make_loss_fn(), o1)
        seq = [s1(ids, ids).item() for _ in range(3)]

        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs["dp_degree"] = 8
        fleet.init(is_collective=True, strategy=strategy)
        paddle.seed(0)
        m2 = GPTForCausalLM(gpt_tiny())
        m2.set_state_dict(sd)
        o2 = opt.SGD(learning_rate=0.01, parameters=m2.parameters())
        s2 = fleet.build_train_step(m2, make_loss_fn(), o2)
        par = [s2(ids, ids).item() for _ in range(3)]
        np.testing.assert_allclose(seq, par, rtol=1e-4, atol=1e-5)

    def test_grad_accumulation(self):
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs["dp_degree"] = 2
        fleet.init(is_collective=True, strategy=strategy)
        paddle.seed(0)
        m = GPTForCausalLM(gpt_tiny())
        o = opt.SGD(learning_rate=1e-2, parameters=m.parameters())
        step = fleet.build_train_step(m, make_loss_fn(), o,
                                      accumulate_steps=2)
        ids = paddle.to_tensor(
            np.random.RandomState(0).randint(0, 1024, size=(8, 16)))
        l0 = step(ids, ids).item()
        l1 = step(ids, ids).item()
        assert np.isfinite(l0) and l1 < l0


class TestPipeline:
    @pytest.mark.heavy
    def test_forward_parity_and_training(self):
        paddle.seed(0)
        mesh = build_mesh(dp=1, pp=4, mp=1, devices=jax.devices()[:4])
        pipe = PipelineLayer(
            [LayerDesc(nn.Linear, 16, 16) for _ in range(8)],
            num_stages=4, loss_fn=lambda o, y: ((o - y) ** 2).mean())
        o = opt.SGD(learning_rate=0.02, parameters=pipe.parameters())
        pp = PipelineParallel(pipe, o, mesh, n_micro=4)
        x = paddle.randn([8, 16])
        y = paddle.randn([8, 16])
        np.testing.assert_allclose(pp.forward(x).numpy(),
                                   pipe(x).numpy(), rtol=1e-4, atol=1e-5)
        l0 = pp.train_batch(x, y).item()
        for _ in range(10):
            l = pp.train_batch(x, y).item()
        assert l < l0

    def test_nonuniform_stages_rejected(self):
        pipe = PipelineLayer(
            [LayerDesc(nn.Linear, 16, 16), LayerDesc(nn.Linear, 16, 8),
             LayerDesc(nn.Linear, 8, 16), LayerDesc(nn.ReLU)],
            num_stages=2)
        o = opt.SGD(parameters=pipe.parameters())
        mesh = build_mesh(dp=1, pp=2, mp=1, devices=jax.devices()[:2])
        with pytest.raises(ValueError):
            PipelineParallel(pipe, o, mesh, n_micro=2)


class TestMPLayers:
    def test_column_row_roundtrip(self):
        from paddle_tpu.distributed.meta_parallel import (
            ColumnParallelLinear, RowParallelLinear)
        paddle.seed(0)
        col = ColumnParallelLinear(8, 16, gather_output=False)
        row = RowParallelLinear(16, 8, input_is_parallel=True)
        x = paddle.randn([4, 8])
        out = row(col(x))
        assert out.shape == [4, 8]
        # eager equivalence to plain two-layer matmul
        ref = (x.numpy() @ col.weight.numpy() + col.bias.numpy()) \
            @ row.weight.numpy() + row.bias.numpy()
        np.testing.assert_allclose(out.numpy(), ref, rtol=1e-4, atol=1e-5)

    def test_vocab_parallel_embedding(self):
        from paddle_tpu.distributed.meta_parallel import \
            VocabParallelEmbedding
        emb = VocabParallelEmbedding(100, 16)
        ids = paddle.to_tensor(np.array([[1, 5], [7, 99]]))
        assert emb(ids).shape == [2, 2, 16]


class TestRecompute:
    def test_recompute_matches_plain(self):
        from paddle_tpu.distributed.fleet.utils.recompute import recompute
        paddle.seed(0)
        lin = nn.Linear(8, 8)
        x = paddle.randn([4, 8])

        from paddle_tpu.jit.api import functional_call, state_arrays
        params, _ = state_arrays(lin)

        def with_remat(ps):
            def f(p):
                def inner(xx):
                    return functional_call(lin, p, {}, (xx,))
                return jax.checkpoint(inner)(x.value).sum()
            return f(ps)

        def plain(ps):
            return functional_call(lin, ps, {}, (x.value,)).sum()

        g1 = jax.grad(with_remat)(params)
        g2 = jax.grad(plain)(params)
        for k in params:
            np.testing.assert_allclose(np.asarray(g1[k]),
                                       np.asarray(g2[k]), rtol=1e-5)


class TestAutoParallel:
    def test_shard_tensor(self):
        from paddle_tpu.distributed import shard_tensor, ProcessMesh
        mesh = ProcessMesh(shape=(4, 2), dim_names=["x", "y"])
        t = paddle.ones([8, 4])
        shard_tensor(t, mesh, ["x", None])
        assert "x" in str(t.value.sharding.spec)


class TestCollectivesAPI:
    def test_spmd_psum(self):
        from paddle_tpu.distributed import psum
        from jax.sharding import PartitionSpec as P
        mesh = build_mesh(dp=8)

        def f(x):
            return psum(x, "dp")
        from jax import shard_map
        out = shard_map(f, mesh=mesh, in_specs=P("dp"),
                        out_specs=P())(jnp.arange(8.0))
        assert float(out[0]) == 28.0

    def test_eager_api_parity(self):
        import paddle_tpu.distributed as dist
        t = paddle.ones([4])
        dist.all_reduce(t)
        lst = []
        dist.all_gather(lst, t)
        assert len(lst) == 1
        dist.broadcast(t, 0)
        assert dist.get_world_size() == 8


class TestZeROStages:
    """Real ZeRO stage-2/3 behavior (ref sharding_stage2.py:43,
    sharding_stage3.py:51): stage selection changes the compiled program
    (reduce-scatter / sharded param storage) without changing numerics."""

    def _build(self, stage, lr=0.01):
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs["dp_degree"] = 4
        strategy.hybrid_configs["sharding_degree"] = 2
        fleet.init(is_collective=True, strategy=strategy)
        paddle.seed(0)
        m = GPTForCausalLM(gpt_tiny())
        o = opt.AdamW(learning_rate=lr, parameters=m.parameters())
        return fleet.build_train_step(m, make_loss_fn(), o,
                                      sharding_stage=stage)

    @pytest.mark.heavy

    def test_stage2_grads_constrained_sharded(self):
        """Stage-2 pins gradients to the 'sharding' axis: the compiled
        update must run on grad SHARDS (sliced shapes), with the grad
        sync lowered as all-reduce+slice — the pair the TPU
        ReduceScatterCreator pass fuses into reduce-scatter (the CPU
        pipeline keeps them separate, so we assert the pattern, not the
        fused op name) — the optimizer state it leaves must STAY on the
        'sharding' axis and the parameters come back at their own specs.
        (Asserted on .sharding, not on the lowered text: the fused
        epilogue takes the grads into a shard_map whose in_specs ARE the
        zero specs, so jax 0.9 prints no separate constraint ops.)"""
        step = self._build(2)
        ids = paddle.to_tensor(
            np.random.RandomState(0).randint(0, 1024, size=(8, 16)))
        assert sum("sharding" in str(s)
                   for s in step.zero_specs.values()) >= 20
        hlo = step.compiled_text(ids, ids)
        # qkv grad [64,192] over sharding=2 -> update math sees [32,192]
        assert "f32[32,192]" in hlo, "update does not run on grad shards"
        assert ("reduce-scatter" in hlo) or ("all-reduce" in hlo)
        step(ids, ids)
        pk = "gpt.h.0.attn.qkv_proj.weight"
        for leaf in jax.tree.leaves(step.opt_state[pk]):
            assert "sharding" in str(leaf.sharding.spec), leaf.sharding
        assert "sharding" not in str(step.params[pk].sharding.spec)

    @pytest.mark.heavy

    def test_stage3_params_stored_sharded(self):
        step = self._build(3)
        pk = "gpt.h.0.attn.qkv_proj.weight"
        assert "sharding" in str(step.params[pk].sharding.spec)
        ids = paddle.to_tensor(
            np.random.RandomState(0).randint(0, 1024, size=(8, 16)))
        hlo = step.compiled_text(ids, ids)
        assert "all-gather" in hlo, "stage-3 must all-gather params at use"

    @pytest.mark.heavy
    def test_stages_numerics_match(self):
        ids = paddle.to_tensor(
            np.random.RandomState(0).randint(0, 1024, size=(8, 16)))
        losses = {}
        for stage in (1, 2, 3):
            step = self._build(stage)
            losses[stage] = [step(ids, ids).item() for _ in range(3)]
        np.testing.assert_allclose(losses[1], losses[2], rtol=1e-4,
                                   atol=1e-5)
        np.testing.assert_allclose(losses[1], losses[3], rtol=1e-4,
                                   atol=1e-5)

    @pytest.mark.heavy

    def test_wrappers_select_behavior(self):
        """ShardingStage3(layer) marker must flow into the train step."""
        from paddle_tpu.distributed.meta_parallel.sharding.sharding_stage \
            import ShardingStage3
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs["dp_degree"] = 4
        strategy.hybrid_configs["sharding_degree"] = 2
        fleet.init(is_collective=True, strategy=strategy)
        paddle.seed(0)
        m = ShardingStage3(GPTForCausalLM(gpt_tiny()))
        o = opt.AdamW(learning_rate=0.01, parameters=m.parameters())
        step = fleet.build_train_step(m, make_loss_fn(), o)
        assert step.sharding_stage == 3
        pk = "gpt.h.0.attn.qkv_proj.weight"
        assert "sharding" in str(step.params[pk].sharding.spec)


class TestAutoParallel:
    """shard_tensor/shard_op/Planner (ref auto_parallel/interface.py:34,73
    + planner.py — GSPMD propagation is the TPU-native planner)."""

    def test_shard_op_constrains_inputs_and_outputs(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from paddle_tpu.distributed.auto_parallel import (shard_op,
                                                          ProcessMesh)
        pm = ProcessMesh(shape=(8,), dim_names=["x"])

        def matmul(a, b):
            return a @ b

        sharded = shard_op(matmul, pm, in_shard_specs=[P("x", None), None],
                           out_shard_specs=P("x", None))

        def f(a, b):
            return sharded(a, b)

        a = jnp.ones((16, 8))
        b = jnp.ones((8, 4))
        out = jax.jit(f)(a, b)
        np.testing.assert_allclose(np.asarray(out), 8.0 * np.ones((16, 4)))
        txt = jax.jit(f).lower(a, b).as_text()
        assert "sharding" in txt  # constraints present in the program

    def test_planner_assigns_shardings(self):
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from paddle_tpu.distributed.auto_parallel import plan, ProcessMesh
        pm = ProcessMesh(shape=(8,), dim_names=["dp"])

        def step(x, w):
            return jnp.tanh(x @ w).sum()

        x = jnp.ones((32, 16))
        w = jnp.ones((16, 16))
        result = plan(step, x, w, process_mesh=pm,
                      in_specs=[P("dp", None), None])
        ins = result.input_shardings
        assert ins is not None
        out = result(x, w)
        np.testing.assert_allclose(float(np.asarray(out)),
                                   float(np.tanh(16.0) * 32 * 16))


class TestSequenceParallel:
    """Sequence-parallel GPT training through fleet: seq dim sharded over
    'sp', attention as ring attention (exact) — long-context first-class
    (SURVEY §6). Loss must match the non-sp run bit-for-bit-ish."""

    def _run(self, sep_degree, sequence_parallel, dp=2):
        from paddle_tpu.models.gpt import GPTConfig
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs["dp_degree"] = dp
        strategy.hybrid_configs["sep_degree"] = sep_degree
        fleet.init(is_collective=True, strategy=strategy)
        paddle.seed(0)
        cfg = GPTConfig(vocab_size=256, hidden_size=32, num_layers=2,
                        num_heads=4, max_position_embeddings=64,
                        dropout=0.0, sequence_parallel=sequence_parallel)
        m = GPTForCausalLM(cfg)
        o = opt.SGD(learning_rate=0.01, parameters=m.parameters())
        step = fleet.build_train_step(m, make_loss_fn(), o)
        ids = paddle.to_tensor(
            np.random.RandomState(0).randint(0, 256, size=(8, 32)))
        return step, [step(ids, ids).item() for _ in range(2)]

    @pytest.mark.heavy
    def test_ring_matches_dense(self):
        _, base = self._run(sep_degree=1, sequence_parallel=False, dp=2)
        _, ring = self._run(sep_degree=4, sequence_parallel=True, dp=2)
        np.testing.assert_allclose(base, ring, rtol=1e-4, atol=1e-5)

    @pytest.mark.heavy
    def test_seq_dim_sharded_and_ring_in_hlo(self):
        step, _ = self._run(sep_degree=4, sequence_parallel=True, dp=2)
        assert "sp" in str(step.batch_sharding.spec)
        ids = paddle.to_tensor(
            np.random.RandomState(0).randint(0, 256, size=(8, 32)))
        hlo = step.compiled_text(ids, ids)
        assert "collective-permute" in hlo, "ring hops must be ppermute"


class TestFleetPipelineRouting:
    """fleet.build_train_step must route PipelineLayer models to the
    PipelineParallel engine (ref fleet.distributed_model wrap) and refuse
    pp_degree>1 for plain layers instead of silently replicating."""

    def test_pipeline_layer_routed(self):
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs["pp_degree"] = 4
        strategy.pipeline_configs["accumulate_steps"] = 4
        strategy.pipeline_configs["schedule_mode"] = "1F1B"
        fleet.init(is_collective=True, strategy=strategy)
        paddle.seed(0)
        pipe = PipelineLayer(
            [LayerDesc(nn.Linear, 16, 16) for _ in range(4)],
            num_stages=4, loss_fn=lambda o, y: ((o - y) ** 2).mean())
        o = opt.SGD(learning_rate=0.02, parameters=pipe.parameters())
        step = fleet.build_train_step(pipe, None, o)
        assert step.engine.schedule == "1f1b"
        x = paddle.randn([8, 16])
        y = paddle.randn([8, 16])
        l0 = step(x, y).item()
        for _ in range(5):
            l = step(x, y).item()
        assert np.isfinite(l) and l < l0

    def test_plain_layer_with_pp_rejected(self):
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs["pp_degree"] = 2
        fleet.init(is_collective=True, strategy=strategy)
        paddle.seed(0)
        m = GPTForCausalLM(gpt_tiny())
        o = opt.SGD(learning_rate=0.01, parameters=m.parameters())
        with pytest.raises(ValueError, match="PipelineLayer"):
            fleet.build_train_step(m, make_loss_fn(), o)

    def test_stage_mesh_mismatch_rejected(self):
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs["pp_degree"] = 2
        fleet.init(is_collective=True, strategy=strategy)
        paddle.seed(0)
        pipe = PipelineLayer(
            [LayerDesc(nn.Linear, 8, 8) for _ in range(4)],
            num_stages=4, loss_fn=lambda o, y: ((o - y) ** 2).mean())
        o = opt.SGD(parameters=pipe.parameters())
        with pytest.raises(ValueError, match="pp"):
            fleet.build_train_step(pipe, None, o)


class TestAutoParallelPlanner:
    """Measured planner (VERDICT r3 #8): plan(search=True) must pick a
    sharded input layout over replicated for a big matmul — ranked by
    XLA's own cost_analysis, the role of the reference's
    auto_parallel/planner.py + cost_model.py."""

    def test_search_picks_sharded_over_replicated(self):
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from paddle_tpu.distributed.auto_parallel import Planner

        mesh = Mesh(np.array(jax.devices()).reshape(8), ("dp",))
        planner = Planner(mesh)
        a = jnp.ones((1024, 512), jnp.float32)
        b = jnp.ones((512, 256), jnp.float32)

        result = planner.plan(lambda x, y: x @ y, a, b, search=True)
        # the chosen plan shards at least one operand over dp
        assert any("dp" in str(s) for s in result.chosen_specs), \
            result.chosen_specs
        # and beats fully-replicated in the measured ranking
        rep_cost = dict((tuple(str(x) for x in specs), c)
                        for specs, c in result.search_report)
        rep_key = (str(P()), str(P()))
        assert rep_key in rep_cost
        best_specs, best_cost = result.search_report[0]
        assert best_cost < rep_cost[rep_key], result.search_report[:3]
        # the winning plan actually executes
        out = result(a, b)
        np.testing.assert_allclose(np.asarray(out)[:2, :2], 512.0)
