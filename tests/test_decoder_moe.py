"""The spec-driven decoder (models/decoder.py) and the dropless expert
layer (incubate/moe.py) against the plain reference the benchmark judges
them by (benchmarks/references/glm_moe.py), at a tiny size of the
benchmark cell's shape: 1 dense + 2 expert layers, 8 routed experts of
which 2 are held, top-2, a shared expert, latent attention with rotary —
every mechanism present."""
import copy
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmarks.lib import correct as C
from benchmarks.lib import program as P
from benchmarks.lib import train as T
from benchmarks.lib.reftrain import leaf_norms, reference_train
from benchmarks.references import glm_moe as ref
from benchmarks.references.common import weights_from_seed
from paddle_tpu.incubate import moe as M
from paddle_tpu.models.decoder import LatentAttention, glm_4_7_flash_ep8

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=3, num_attention_heads=4, q_lora_rank=32,
            kv_lora_rank=16, qk_nope_head_dim=24, qk_rope_head_dim=8,
            v_head_dim=32, moe_intermediate_size=48, n_routed_experts=2,
            router_experts=8, local_expert_start=2, num_experts_per_tok=2)
HP = {"lr": 1e-4, "beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8,
      "weight_decay": 0.01}
CELL = {"batch": 2, "seq": 32, "optimizer": HP}
SEED = 3000000005
# bf16 program against the float32 reference at hidden 64, three steps.
# Sound runs read loss gaps of 1e-5 to 4e-5, grad_norm_gap about 0.04
# (a flipped near-tied route moves an expert's few tokens), change_norm_gap
# 0.005; the fp8 control reads loss gaps over 1e-4 and grad_norm_gap over
# 0.1, and must fail
BF16_LIMITS = {"loss1_gap": 1e-4, "loss2_gap": 1e-4, "loss3_gap": 1e-4,
               "grad_norm_gap": 0.08, "change_norm_gap": 0.05}
F32_LIMITS = {k: 1e-5 for k in BF16_LIMITS}


def tiny_config(dtype):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "glm-4.7-flash-ep8.json")) as f:
        cfg = json.load(f)
    cfg.update(TINY)
    cfg["dtype"] = dtype
    cfg["program"]["kwargs"] = dict(TINY)
    return cfg


def program_three_steps(config):
    """What benchmarks/lib/train.py does before its window, through the
    same functions (float32 has no master copy, so its readings are taken
    from the parameters themselves)."""
    spec = ref.param_spec(config)
    model = P.build_model(config)
    P.install_weights(model, weights_from_seed(spec, SEED, config["dtype"]))
    step = T.build_step(CELL, model)
    batches = T.make_batches(SEED, config["vocab_size"], CELL["batch"],
                             CELL["seq"], T.CHECK_STEPS)
    losses, first = [], None
    for toks in batches:
        losses.append(float(step(paddle.to_tensor(toks[:, :-1]),
                                 paddle.to_tensor(toks[:, 1:])).item()))
        if first is None:
            first = leaf_norms({
                k: (s["state"][0] if isinstance(s, dict) else s[0])
                for k, s in step.opt_state.items()})
    w0 = weights_from_seed(spec, SEED, config["dtype"])
    if config["dtype"] == "bfloat16":
        prog = T.program_readings(step, w0, losses, HP, first)
    else:
        delta = {k: v - P.leaf_of(w0, k) for k, v in step.params.items()}
        prog = {"losses": losses, "change_norms": leaf_norms(delta),
                "grad_norms": {k: n / (1 - HP["beta1"])
                               for k, n in first.items()}}
    return prog, batches, step


@pytest.fixture(scope="module")
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def runs():
    """{dtype: (readings, batches, the moe.* counters after the steps)}
    — one program run a dtype for the tests below."""
    from paddle_tpu.profiler import monitor
    out = {}
    for dtype in ("float32", "bfloat16"):
        monitor.reset_metrics()
        with jax.default_matmul_precision(
                "highest" if dtype == "float32" else "default"):
            prog, batches, step = program_three_steps(tiny_config(dtype))
        step.flush_step_counters()
        assert step._counter_names == M.STEP_COUNTERS
        out[dtype] = (prog, batches, {
            k: v for k, v in monitor.metrics_snapshot().items()
            if k.startswith("moe.")})
    return out


@pytest.mark.parametrize("dtype,limits", [("float32", F32_LIMITS),
                                          ("bfloat16", BF16_LIMITS)])
def test_three_steps_against_the_reference(runs, dtype, limits):
    config = tiny_config(dtype)
    names = {n for n, _ in P.build_model(config).named_parameters()}
    leaves = set()
    for k, (shape, _) in ref.param_spec(config).items():
        leaves |= {k.replace(".h.*.", f".h.{i}.") for i in range(shape[0])} \
            if ".h.*." in k else {k}
    assert names == leaves      # param_spec IS the program's parameters
    prog, batches, _ = runs[dtype]
    sound = reference_train(ref, config, SEED, batches, HP, micro=2)
    numbers, notes = C.train_numbers(prog, sound)
    ok, rows = C.judge(numbers, limits)
    assert ok, (rows, notes)
    if dtype == "bfloat16":
        control = reference_train(ref, config, SEED, batches, HP, micro=2,
                                  prec="fp8")
        ok, rows = C.judge(C.train_numbers(control, sound)[0], limits)
        assert not ok, rows
        frozen = dict(prog, change_norms={k: 0.0
                                          for k in prog["change_norms"]})
        assert not C.judge(C.train_numbers(frozen, sound)[0], limits)[0]


def test_counters_reach_the_monitor(runs):
    """Fed from the in-graph vector, once a step, off the step's path."""
    got = runs["float32"][2]
    # 3 steps x 64 tokens x top-2 x 2 expert layers
    assert got["moe.assignments"] == 3 * 64 * 2 * 2
    assert 0 < got["moe.local_assignments"] < got["moe.assignments"]
    assert got["moe.expert_load_max"] * 2 >= got["moe.local_assignments"]
    assert got["moe.dropped"] == 0


def test_a_model_that_records_no_counter_compiles_to_the_plain_step():
    """The per-step program of a GPT model is, op for op, the plain step
    function's (the one run_steps scans): the counter path adds nothing
    where no layer records."""
    import re
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    model = GPTForCausalLM(GPTConfig(vocab_size=128, hidden_size=32,
                                     num_layers=2, num_heads=2,
                                     max_position_embeddings=32))
    step = T.build_step(CELL, model)
    x = paddle.to_tensor(np.zeros((2, 16), np.int32))
    _, args = step._prep((x, x), 1)
    plain = jax.jit(step._step_fn, donate_argnums=(0, 1, 2)).lower(
        *args).compile().as_text()

    def ops(text):      # the instructions, without source locations
        text = re.sub(r", metadata=\{[^}]*\}", "", text)
        text = re.sub(r"step_fn(_single)?", "step_fn", text)
        return [l for l in text.splitlines()
                if " = " in l or l.startswith(("ENTRY", "HloModule", "%"))]

    assert ops(step.compiled_text(x, x)) == ops(plain)
    assert len(ops(plain)) > 1000
    assert step._counter_names == ()


# ---- the share of guide section 4 -------------------------------------
def test_shares_of_a_layer_sum_to_the_uncut_layer(highest):
    """The routed parts that the four chips of a group compute (2 experts
    each), with the shared expert counted once, add up to what the uncut
    reference gives for the whole layer."""
    D, F, E, k, N = 32, 48, 8, 2, 64
    cfg = dict(num_experts_per_tok=k, norm_topk_prob=True,
               routed_scaling_factor=1.8)
    key = jax.random.PRNGKey(0)
    nrm = lambda i, *s: 0.3 * jax.random.normal(jax.random.fold_in(key, i),
                                                s, jnp.float32)
    x = nrm(0, N, D)
    whole = {"mlp.router.weight": nrm(1, D, E),
             "mlp.experts_gate": nrm(2, E, D, F),
             "mlp.experts_up": nrm(3, E, D, F),
             "mlp.experts_down": nrm(4, E, F, D),
             "mlp.shared.gate_proj.weight": nrm(5, D, F),
             "mlp.shared.up_proj.weight": nrm(6, D, F),
             "mlp.shared.down_proj.weight": nrm(7, F, D)}
    want = ref.expert_layer(x, whole, cfg, "f32", held=(0, E))
    shared = ref.gated(x, whole["mlp.shared.gate_proj.weight"],
                       whole["mlp.shared.up_proj.weight"],
                       whole["mlp.shared.down_proj.weight"], "f32")
    total = shared
    for first in range(0, E, 2):
        layer = M.DroplessMoE(D, F, E, k, local_experts=range(first,
                                                              first + 2),
                              n_shared_experts=1, routed_scaling_factor=1.8)
        layer.router.weight.set_value(whole["mlp.router.weight"])
        for n in ("gate", "up", "down"):
            getattr(layer, f"experts_{n}").set_value(
                whole[f"mlp.experts_{n}"][first:first + 2])
            getattr(layer.shared, f"{n}_proj").weight.set_value(
                whole[f"mlp.shared.{n}_proj.weight"])
        share = layer(paddle.to_tensor(x)).value
        # the reference, given the same share, agrees with the program
        np.testing.assert_allclose(
            share, ref.expert_layer(x, {**whole, **{
                f"mlp.experts_{n}": whole[f"mlp.experts_{n}"][first:first + 2]
                for n in ("gate", "up", "down")}}, cfg, "f32",
                held=(first, 2)), atol=2e-5)
        total = total + (share - shared)
    np.testing.assert_allclose(total, want, atol=5e-5)


# ---- routing ------------------------------------------------------------
def routed(bias=None, N=200, D=16, E=8, k=4, seed=1):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (N, D), jnp.float32)
    rw = jax.random.normal(jax.random.fold_in(key, 1), (D, E), jnp.float32)
    bias = jnp.zeros(E) if bias is None else jnp.asarray(bias, jnp.float32)
    return x, rw, M.route_tokens(x, rw, bias, k, 1.8, True)


def test_routing_picks_distinct_experts_with_weights_that_sum_to_scale():
    _, _, (chosen, w) = routed()
    assert chosen.shape == (200, 4) and chosen.dtype == jnp.int32
    assert all(len(set(map(int, row))) == 4 for row in np.asarray(chosen))
    np.testing.assert_allclose(w.sum(-1), 1.8, rtol=1e-5)
    assert float(w.min()) > 0


def test_selection_bias_changes_who_is_chosen_and_not_the_weight():
    x, rw, (chosen0, _) = routed()
    bias = np.zeros(8, np.float32)
    bias[5] = 10.0                      # expert 5 now wins every token
    _, _, (chosen, w) = routed(bias)
    assert (np.asarray(chosen) == 5).any(-1).all()
    assert not (np.asarray(chosen0) == 5).any(-1).all()
    s = jax.nn.sigmoid(jnp.dot(x, rw, precision="highest"))
    picked = jnp.take_along_axis(s, chosen, -1)      # plain scores, no bias
    np.testing.assert_allclose(
        w, picked / picked.sum(-1, keepdims=True) * 1.8, rtol=1e-5)


def dense_experts(x, chosen, w, wg, wu, wd, first):
    E = 8
    per = jnp.zeros((x.shape[0], E)).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(w)
    return sum((jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e]
               * per[:, first + e][:, None] for e in range(wg.shape[0]))


@pytest.mark.parametrize("load", ["uniform", "empty_group", "one_expert",
                                  "none_held"])
def test_no_assignment_to_a_held_expert_is_lost(highest, load):
    """Every load, the worst included: all tokens on one held expert, a
    held expert nobody chose, no token for any held expert."""
    N, D, F, k, first, held = 96, 16, 24, 2, 2, 3
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (N, D), jnp.float32)
    wg, wu, wd = (0.3 * jax.random.normal(jax.random.fold_in(key, i), s)
                  for i, s in ((1, (held, D, F)), (2, (held, D, F)),
                               (3, (held, F, D))))
    rng = np.random.RandomState(0)
    if load == "uniform":
        chosen = np.stack([rng.permutation(8)[:k] for _ in range(N)])
    elif load == "empty_group":          # nobody chooses held expert 3
        chosen = np.stack([rng.permutation([0, 1, 2, 4, 5, 6, 7])[:k]
                           for _ in range(N)])
    elif load == "one_expert":           # every token on held expert 2
        chosen = np.stack([[2, rng.choice([0, 1, 5, 6, 7])]
                           for _ in range(N)])
    else:
        chosen = np.stack([rng.permutation([0, 1, 5, 6, 7])[:k]
                           for _ in range(N)])
    chosen = jnp.asarray(chosen, jnp.int32)
    w = jnp.asarray(rng.uniform(0.2, 1.0, (N, k)), jnp.float32)
    y, counters = M.dropless_experts(x, chosen, w, wg, wu, wd, first)
    want = dense_experts(x, chosen, w, wg, wu, wd, first)
    np.testing.assert_allclose(y, want, atol=2e-5)
    n_held = int(((chosen >= first) & (chosen < first + held)).sum())
    sizes = [int((chosen == first + e).sum()) for e in range(held)]
    assert list(map(int, counters)) == [N * k, n_held, max(sizes), 0,
                                        N * min(k, held)]
    # and the gradients, through the gathers that stand for scatters
    f = lambda fn: jax.grad(lambda *a: (fn(a[0], chosen, a[1], *a[2:],
                                           first) ** 2).sum(),
                            (0, 1, 2, 3, 4))(x, w, wg, wu, wd)
    got = f(lambda *a: M.dropless_experts(*a)[0])
    for a, b in zip(got, f(dense_experts)):
        np.testing.assert_allclose(a, b, atol=1e-4 * (1 + float(
            jnp.abs(b).max())))


# ---- latent attention ----------------------------------------------------
def test_latent_attention_matches_the_reference_rotary_included(highest):
    attn = LatentAttention(glm_4_7_flash_ep8(**TINY))
    config = tiny_config("float32")
    shapes = ref.layer_shapes(config, False)
    key = jax.random.PRNGKey(3)
    p = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        if not name.startswith("self_attn."):
            continue
        v = jax.random.normal(jax.random.fold_in(key, i), shape) \
            * (1.0 if "layernorm" in name else 0.1) \
            + (1.0 if "layernorm" in name else 0.0)
        p[name] = v
        mod = attn
        for part in name.split(".")[1:-1]:
            mod = getattr(mod, part)
        mod.weight.set_value(v)
    x = jax.random.normal(jax.random.fold_in(key, 99), (2, 48, 64))
    got = attn(paddle.to_tensor(x)).value
    want = jnp.stack([ref.attention(r, p, config, "f32") for r in x])
    np.testing.assert_allclose(got, want, atol=2e-5)
    # positions matter: the same tokens shifted by one give other outputs
    rolled = attn(paddle.to_tensor(jnp.roll(x, 1, axis=1))).value
    assert float(jnp.abs(jnp.roll(rolled, -1, axis=1)[:, 1:-1]
                         - got[:, 1:-1]).max()) > 1e-3
