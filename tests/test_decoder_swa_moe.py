"""SmallThinker's parts of the spec-driven decoder (models/decoder.py:
grouped-query attention full and windowed, the expert layer routed on the
layer's input; incubate/moe.py: softmax over the chosen logits, ReLU
gates) against the plain reference the benchmark judges them by
(benchmarks/references/smallthinker.py), at a tiny size of the benchmark
cell's shape: one period — a full layer without positions, three window
layers with rotary — 8 routed experts of which 2 are held, top-2, 4 query
heads on 2 key/value heads, a window the sequence overflows."""
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
from benchmarks.references import smallthinker as ref
from benchmarks.references.common import weights_from_seed
from paddle_tpu.incubate import moe as M
from paddle_tpu.models.decoder import (DecoderForCausalLM, DecoderLayer,
                                       smallthinker_21b_ep8)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = dict(vocab_size=256, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, sliding_window_size=8,
            n_routed_experts=2, router_experts=8, local_expert_start=2,
            num_experts_per_tok=2, moe_intermediate_size=48)
# the published names of the same sizes, which the reference reads
PUBLISHED_NAMES = dict(moe_num_primary_experts=2,
                       moe_num_active_primary_experts=2,
                       moe_ffn_hidden_size=48)
HP = {"lr": 1e-4, "beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8,
      "weight_decay": 0.01}
CELL = {"batch": 2, "seq": 32, "optimizer": HP}
SEED = 3000000007
# bf16 program against the float32 reference at hidden 64, three steps.
# Readings over seeds 3000000007-11 (this file's functions, by hand):
# sound runs read loss gaps up to 3.7e-5, grad_norm_gap 0.0015 to 0.017,
# change_norm_gap up to 0.0084; the fp8 control reads a loss gap of 1.1e-4
# to 4.3e-4 on every seed (grad_norm_gap 0.012 to 0.024), and must fail; a
# frozen state reads 1 on change_norm_gap
BF16_LIMITS = {"loss1_gap": 1e-4, "loss2_gap": 1e-4, "loss3_gap": 1e-4,
               "grad_norm_gap": 0.08, "change_norm_gap": 0.05}
F32_LIMITS = {k: 1e-5 for k in BF16_LIMITS}


def tiny_config(dtype):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "smallthinker-21b-ep8.json")) as f:
        cfg = json.load(f)
    cfg.update(TINY, **PUBLISHED_NAMES)
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
    got = runs["float32"][2]
    # 3 steps x 64 tokens x top-2 x 4 layers
    assert got["moe.assignments"] == 3 * 64 * 2 * 4
    assert 0 < got["moe.local_assignments"] < got["moe.assignments"]
    assert got["moe.dropped"] == 0


def test_preset_holds_the_published_widths():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "smallthinker-21b-ep8.json")) as f:
        config = json.load(f)
    cfg = smallthinker_21b_ep8()
    for k, v in config.items():        # build_model's own check
        assert not hasattr(cfg, k) or getattr(cfg, k) == v, k
    pub = config["published"]
    for ours, theirs in (("moe_intermediate_size", "moe_ffn_hidden_size"),
                         ("num_experts_per_tok",
                          "moe_num_active_primary_experts"),
                         ("router_experts", "moe_num_primary_experts"),
                         ("hidden_size", "hidden_size"),
                         ("head_dim", "head_dim"),
                         ("num_attention_heads", "num_attention_heads"),
                         ("num_key_value_heads", "num_key_value_heads"),
                         ("sliding_window_size", "sliding_window_size"),
                         ("rope_theta", "rope_theta"),
                         ("rms_norm_eps", "rms_norm_eps")):
        assert getattr(cfg, ours) == pub[theirs], ours
    assert (cfg.hidden_size, cfg.head_dim, cfg.moe_intermediate_size,
            cfg.sliding_window_size) == (2560, 128, 768, 4096)
    n = cfg.num_hidden_layers
    assert cfg.layers == [("gqa_window" if w else "gqa_full", "moe_pre")
                          for w in pub["sliding_window_layout"][:n]]
    assert pub["rope_layout"] == pub["sliding_window_layout"]
    assert config["reduced"] == ["num_hidden_layers",
                                 "moe_num_primary_experts", "vocab_size"]
    # one chip's share: 370.6 M parameters (ISSUE 31)
    spec = ref.param_spec(config)
    assert sum(int(np.prod(s)) for s, _ in spec.values()) == \
        pytest.approx(370.6e6, rel=1e-3)
    # two periods: everything before the last uniform run is unrolled
    two = smallthinker_21b_ep8(num_hidden_layers=8, **TINY)
    model = DecoderForCausalLM(two)
    assert (len(model.model.lead), len(model.model.h)) == (5, 3)
    assert ref.n_lead({**config, "num_hidden_layers": 8}) == 5


# ---- the share of guide section 4 -------------------------------------
def test_shares_of_a_layer_sum_to_the_uncut_layer(highest):
    """The parts that the four chips of a group compute (2 experts each)
    add up to what the uncut reference gives for the whole layer: there
    is no shared expert to count once."""
    D, F, E, k, N = 32, 48, 8, 2, 64
    cfg = dict(moe_num_active_primary_experts=k, norm_topk_prob=True,
               moe_primary_router_apply_softmax=True)
    key = jax.random.PRNGKey(0)
    nrm = lambda i, *s: 0.3 * jax.random.normal(jax.random.fold_in(key, i),
                                                s, jnp.float32)
    x, r_in = nrm(0, N, D), nrm(8, N, D)
    whole = {"mlp.router.weight": nrm(1, D, E),
             "mlp.experts_gate": nrm(2, E, D, F),
             "mlp.experts_up": nrm(3, E, D, F),
             "mlp.experts_down": nrm(4, E, F, D)}
    want = ref.expert_layer(x, r_in, whole, cfg, "f32", held=(0, E))
    total = 0.0
    for first in range(0, E, 2):
        layer = M.DroplessMoE(D, F, E, k, local_experts=range(first,
                                                              first + 2),
                              scoring="softmax_topk", activation="relu")
        layer.router.weight.set_value(whole["mlp.router.weight"])
        held = {f"mlp.experts_{n}": whole[f"mlp.experts_{n}"][first:first + 2]
                for n in ("gate", "up", "down")}
        for n in ("gate", "up", "down"):
            getattr(layer, f"experts_{n}").set_value(held[f"mlp.experts_{n}"])
        share = layer(paddle.to_tensor(x),
                      router_input=paddle.to_tensor(r_in)).value
        # the reference, given the same share, agrees with the program
        np.testing.assert_allclose(
            share, ref.expert_layer(x, r_in, {**whole, **held}, cfg, "f32",
                                    held=(first, 2)), atol=2e-5)
        total = total + share
    np.testing.assert_allclose(total, want, atol=5e-5)
    # routed on x itself the result differs: the router input is read
    assert not np.allclose(
        want, ref.expert_layer(x, x, whole, cfg, "f32", held=(0, E)),
        atol=1e-3)


# ---- routing ------------------------------------------------------------
def test_softmax_over_the_chosen_logits():
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (100, 16), jnp.float32)
    rw = jax.random.normal(jax.random.fold_in(key, 1), (16, 8), jnp.float32)
    chosen, w = M.route_tokens(x, rw, jnp.zeros(8), 3, 1.0, True,
                               "softmax_topk")
    logits = jnp.dot(x, rw, precision="highest")
    every = jax.nn.softmax(logits, -1)
    picked = jnp.take_along_axis(every, chosen, -1)
    np.testing.assert_array_equal(chosen, jax.lax.top_k(logits, 3)[1])
    # the softmax over all, renormalised over the chosen
    np.testing.assert_allclose(w, picked / picked.sum(-1, keepdims=True),
                               rtol=1e-5)
    np.testing.assert_allclose(w.sum(-1), 1.0, rtol=1e-6)
    _, raw = M.route_tokens(x, rw, jnp.zeros(8), 3, 1.0, False,
                            "softmax_topk")
    np.testing.assert_allclose(raw, picked, rtol=1e-5)
    with pytest.raises(ValueError, match="scoring"):
        M.DroplessMoE(16, 8, 8, 2, scoring="softmax")
    with pytest.raises(ValueError, match="activation"):
        M.DroplessMoE(16, 8, 8, 2, activation="gelu")


def test_relu_gate_and_silu_gate_differ_and_match_their_formulas(highest):
    N, D, F = 32, 16, 24
    key = jax.random.PRNGKey(3)
    nrm = lambda i, *s: jax.random.normal(jax.random.fold_in(key, i), s,
                                          jnp.float32)
    x, wg, wu, wd = nrm(0, N, D), nrm(1, 1, D, F), nrm(2, 1, D, F), \
        nrm(3, 1, F, D)
    chosen = jnp.zeros((N, 1), jnp.int32)
    w = jnp.ones((N, 1), jnp.float32)
    for act in (jax.nn.relu, jax.nn.silu):
        y, counters = M.dropless_experts(x, chosen, w, wg, wu, wd, 0, act)
        np.testing.assert_allclose(
            y, (act(x @ wg[0]) * (x @ wu[0])) @ wd[0], atol=1e-4)
        assert list(np.asarray(counters)) == [N, N, N, 0, N]


# ---- the layer ------------------------------------------------------------
def _layer(spec, seed=0):
    paddle.seed(seed)
    cfg = smallthinker_21b_ep8(**TINY)
    return DecoderLayer(cfg, spec), cfg


def test_a_layer_without_positions_does_not_see_them(highest):
    """The full layer carries no positions: the causal mask is the only
    order it knows, so a query's output does not move when two keys it
    both sees change places. The window layer rotates q and k by their
    positions, and the same swap moves every later output."""
    rng = np.random.default_rng(0)
    x = rng.standard_normal((1, 6, 64)).astype(np.float32)
    swapped = x[:, [1, 0, 2, 3, 4, 5]]      # keys 0 and 1 change places
    full, _ = _layer(("gqa_full", "moe_pre"))
    window, _ = _layer(("gqa_window", "moe_pre"))
    for layer, moves in ((full, False), (window, True)):
        attn = lambda a: layer.self_attn(paddle.to_tensor(a)).value
        a, b = attn(x), attn(swapped)
        # tokens 2..5 see keys {0, 1, ...} in either order
        same = np.allclose(a[:, 2:], b[:, 2:], atol=1e-5)
        assert same != moves
    assert full.self_attn.rotary is None and full.self_attn.window is None
    assert window.self_attn.window == TINY["sliding_window_size"]


def test_the_router_reads_the_layer_input(highest):
    """`chosen` is a function of the layer's input alone: perturbing the
    attention weights moves the layer's output and not a single route."""
    layer, cfg = _layer(("gqa_window", "moe_pre"), seed=1)
    rng = np.random.default_rng(1)
    x = paddle.to_tensor(rng.standard_normal((2, 16, 64)).astype(np.float32))
    seen = []
    real = M.route_tokens

    def spy(xr, *a, **kw):
        out = real(xr, *a, **kw)
        seen.append((np.asarray(xr), np.asarray(out[0])))
        return out

    M.route_tokens, before = spy, None
    try:
        before = layer(x).value
        for p in layer.self_attn.parameters():
            p.set_value(p.value * 1.5 + 0.01)
        after = layer(x).value
    finally:
        M.route_tokens = real
    (in0, chosen0), (in1, chosen1) = seen
    np.testing.assert_array_equal(in0, x.value.reshape(-1, 64))
    np.testing.assert_array_equal(in0, in1)
    np.testing.assert_array_equal(chosen0, chosen1)
    assert not np.allclose(before, after, atol=1e-3)
    # an expert layer of the other family routes on what it transforms
    glm_like = DecoderLayer(smallthinker_21b_ep8(**TINY),
                            ("gqa_window", "moe"))
    assert not glm_like.routes_on_input and layer.routes_on_input
