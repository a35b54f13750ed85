"""What a decoder layer keeps for its backward pass (models/decoder.py
`_REMAT_POLICY`): every matmul that contracts over the full hidden width
runs once in the forward and not again in the backward, the low-rank
up-projections of latent attention do run again, the gradient is the one
the flash-names-only policy gives, and the bytes kept are counted
(profiler.monitor `remat.saved_bytes.<name>`, the compile record's
`remat_saved_bytes`). At the tiny sizes of both presets that
tests/test_decoder_moe.py and tests/test_decoder_swa_moe.py build."""
import collections

import jax
import jax.extend.core as jcore
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.jit.api import functional_call, state_arrays
from paddle_tpu.models import decoder as D
from paddle_tpu.profiler import compile_observatory, monitor

GLM = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
           num_hidden_layers=3, num_attention_heads=4, q_lora_rank=32,
           kv_lora_rank=16, qk_nope_head_dim=24, qk_rope_head_dim=8,
           v_head_dim=32, moe_intermediate_size=48, n_routed_experts=2,
           router_experts=8, local_expert_start=2, num_experts_per_tok=2)
SMALLTHINKER = dict(vocab_size=256, hidden_size=64, num_attention_heads=4,
                    num_key_value_heads=2, head_dim=16,
                    sliding_window_size=8, n_routed_experts=2,
                    router_experts=8, local_expert_start=2,
                    num_experts_per_tok=2, moe_intermediate_size=48)
B, T = 2, 32
F32 = 4
OLD_POLICY = jax.checkpoint_policies.save_only_these_names(
    "flash_out", "flash_lse")


def _model(preset):
    paddle.seed(0)
    if preset == "glm":
        return D.DecoderForCausalLM(D.glm_4_7_flash_ep8(**GLM))
    return D.DecoderForCausalLM(D.smallthinker_21b_ep8(**SMALLTHINKER))


def _layers(model):
    return [*model.model.lead, *model.model.h]


# (preset, layer index, layer kind): the four kinds the two presets build
KINDS = [("glm", 0, "mla+dense"), ("glm", 1, "mla+moe"),
         ("smallthinker", 0, "gqa_full+moe_pre"),
         ("smallthinker", 1, "gqa_window+moe_pre")]
# the names each kind keeps beside the flash kernel's, and their widths
# at the tiny sizes: latent attention its latents (q_lora_rank; kv_lora_
# rank + the rotary slice), grouped-query attention q, k, v, every layer
# the residual after the mixer, a gated feed-forward (the dense layer,
# the shared expert) gate and up
KEPT = {
    "mla+dense": {"mla_q_latent": 32, "mla_kv_latent": 24, "layer_h": 64,
                  "mlp_gate": 128, "mlp_up": 128},
    "mla+moe": {"mla_q_latent": 32, "mla_kv_latent": 24, "layer_h": 64,
                "mlp_gate": 48, "mlp_up": 48},
    "gqa_full+moe_pre": {"attn_q": 64, "attn_k": 32, "attn_v": 32,
                         "layer_h": 64},
    "gqa_window+moe_pre": {"attn_q": 64, "attn_k": 32, "attn_v": 32,
                           "layer_h": 64},
}


def _layer_loss(layer, policy):
    p, b = state_arrays(layer)

    def loss(p, x):
        return functional_call(layer, p, b, (x,), training=True).sum()

    return jax.checkpoint(loss, policy=policy), p


def _x():
    return jnp.asarray(np.random.RandomState(0).randn(B, T, 64),
                       jnp.float32)


@pytest.mark.parametrize("preset,index,kind", KINDS,
                         ids=[k for _, _, k in KINDS])
def test_a_layer_keeps_its_kinds_values(preset, index, kind, remat_saved):
    """The names the policy keeps in one layer are the set its mixer and
    feed-forward kinds tag, and what the backward pass reads beside the
    layer's arguments is exactly those values."""
    layer = _layers(_model(preset))[index]
    asked = set()

    def recording(prim, *avals, **params):
        saves = D._REMAT_POLICY(prim, *avals, **params)
        if saves and "name" in params:
            asked.add(params["name"])
        return saves

    f, p = _layer_loss(layer, recording)
    kept = remat_saved(f, p, _x())
    assert asked == set(KEPT[kind])
    # each [B, T, ...] of its width a token; a gated feed-forward's gate
    # reaches the backward pass through its jitted activation
    shapes = [tuple(int(d) for d in aval[aval.index("[") + 1:-1].split(","))
              for aval, _ in kept]
    assert all(s[:2] == (B, T) for s in shapes), kept
    assert sorted(int(np.prod(s[2:])) for s in shapes) == \
        sorted(KEPT[kind].values())


# -- the matmuls a weight meets in the lowered gradient ------------------

# ops that pass a weight (or its gradient) on unchanged in value
_VIEWS = {"transpose", "convert_element_type", "reshape", "copy", "copy_p",
          "squeeze", "expand_dims", "broadcast_in_dim", "reduce_precision"}


def _inner(eqn):
    return [v.jaxpr if isinstance(v, jcore.ClosedJaxpr) else v
            for v in eqn.params.values()
            if isinstance(v, (jcore.ClosedJaxpr, jcore.Jaxpr))]


def _as_operand(jaxpr, tags, counts):
    """Count, per weight, the dot_generals it (or a view of it) enters
    as an operand; returns the tags of jaxpr's variables."""
    tags = dict(tags)
    for eqn in jaxpr.eqns:
        ins = [tags.get(v, frozenset()) if isinstance(v, jcore.Var)
               else frozenset() for v in eqn.invars]
        if eqn.primitive.name == "dot_general":
            for w in frozenset().union(*ins):
                counts[w] += 1
            continue
        if eqn.primitive.name in _VIEWS:
            tags[eqn.outvars[0]] = ins[0]
            continue
        for sub in _inner(eqn):
            if len(sub.invars) != len(eqn.invars):
                continue
            out = _as_operand(sub, {v: t for v, t in zip(sub.invars, ins)
                                    if t}, counts)
            if len(sub.outvars) == len(eqn.outvars):
                for o, io in zip(eqn.outvars, sub.outvars):
                    if isinstance(io, jcore.Var) and out.get(io):
                        tags[o] = out[io]
    return tags


def _as_gradient(jaxpr, sinks, counts):
    """Count, per weight, the dot_generals whose result becomes its
    gradient (through views and sums); `sinks` tags jaxpr's outputs.
    Returns the tags reaching jaxpr's inputs."""
    sinks = dict(sinks)
    for eqn in reversed(jaxpr.eqns):
        outs = frozenset().union(*(sinks.get(v, frozenset())
                                   for v in eqn.outvars))
        if not outs:
            continue
        name = eqn.primitive.name
        if name == "dot_general":
            for w in outs:
                counts[w] += 1
            continue
        if name in _VIEWS or name in ("add_any", "add"):
            for v in eqn.invars:
                if isinstance(v, jcore.Var):
                    sinks[v] = sinks.get(v, frozenset()) | outs
            continue
        for sub in _inner(eqn):
            if len(sub.outvars) != len(eqn.outvars) \
                    or len(sub.invars) != len(eqn.invars):
                continue
            inner = {io: sinks[o] for o, io in zip(eqn.outvars, sub.outvars)
                     if isinstance(io, jcore.Var) and sinks.get(o)}
            got = _as_gradient(sub, inner, counts)
            for v, iv in zip(eqn.invars, sub.invars):
                if isinstance(v, jcore.Var) and got.get(iv):
                    sinks[v] = sinks.get(v, frozenset()) | got[iv]
    return sinks


def _matmuls_met(layer, policy):
    """{weight: matmuls it meets in the gradient of one layer}: as an
    operand (the forward, a recompute, the input's gradient) and as the
    result (its own gradient)."""
    f, p = _layer_loss(layer, policy)
    closed = jax.make_jaxpr(jax.grad(f))(p, _x())
    names = sorted(p)   # a dict flattens in sorted key order
    counts = collections.Counter()
    _as_operand(closed.jaxpr, {v: frozenset([k]) for v, k in
                               zip(closed.jaxpr.invars, names)}, counts)
    _as_gradient(closed.jaxpr, {v: frozenset([k]) for v, k in
                                zip(closed.jaxpr.outvars, names)}, counts)
    return {k: counts[k] for k in names if p[k].ndim == 2}


# every matmul weight of a kind over the full hidden width, and the ones
# that run again from a kept value (latent attention's up-projections;
# the router, from the kept residual's norm)
FULL_WIDTH = {
    "mla+dense": ("self_attn.q_a_proj", "self_attn.kv_a_proj_with_mqa",
                  "self_attn.o_proj", "mlp.gate_proj", "mlp.up_proj"),
    "mla+moe": ("self_attn.q_a_proj", "self_attn.kv_a_proj_with_mqa",
                "self_attn.o_proj", "mlp.shared.gate_proj",
                "mlp.shared.up_proj"),
    "gqa_full+moe_pre": ("self_attn.q_proj", "self_attn.k_proj",
                         "self_attn.v_proj", "self_attn.o_proj"),
    "gqa_window+moe_pre": ("self_attn.q_proj", "self_attn.k_proj",
                           "self_attn.v_proj", "self_attn.o_proj"),
}
RUN_AGAIN = {
    "mla+dense": ("self_attn.q_b_proj", "self_attn.kv_b_proj"),
    "mla+moe": ("self_attn.q_b_proj", "self_attn.kv_b_proj", "mlp.router"),
    "gqa_full+moe_pre": (), "gqa_window+moe_pre": (),
}


@pytest.mark.parametrize("preset,index,kind", KINDS,
                         ids=[k for _, _, k in KINDS])
def test_full_width_matmuls_run_once(preset, index, kind):
    """A full-width projection weight meets 3 matmuls in the gradient
    (forward, the input's gradient, its own), 4 under the old policy,
    which ran the forward's again; latent attention's up-projections
    still meet 4; the down-projection never ran again."""
    layer = _layers(_model(preset))[index]
    new = _matmuls_met(layer, D._REMAT_POLICY)
    old = _matmuls_met(layer, OLD_POLICY)
    for w in FULL_WIDTH[kind]:
        assert (new[w + ".weight"], old[w + ".weight"]) == (3, 4), w
    for w in RUN_AGAIN[kind]:
        assert new[w + ".weight"] == old[w + ".weight"] == 4, w
    down = [k for k in new if k.endswith("down_proj.weight")]
    assert all(new[k] == old[k] == 3 for k in down), down


# -- the same gradient ---------------------------------------------------

def _loss_and_grads(model, policy, monkeypatch):
    monkeypatch.setattr(D, "_REMAT_POLICY", policy)
    params, buffers = state_arrays(model)
    ids = jnp.asarray(np.random.RandomState(1).randint(0, 256, (B, T)),
                      jnp.int32)

    def loss(p):
        logits = functional_call(model, p, buffers, (ids[:, :-1],),
                                 training=True)
        return jnp.mean(jax.nn.log_softmax(logits)[..., 0])

    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(loss))(params)


@pytest.mark.parametrize("preset", ["glm", "smallthinker"])
def test_gradient_is_the_old_policys(preset, monkeypatch):
    """Loss and every gradient leaf, lead layer (unrolled) and scanned
    run alike, equal the flash-names-only policy's in float32."""
    model = _model(preset)
    assert len(model.model.lead) == 1 and len(model.model.h) > 1
    loss, grads = _loss_and_grads(model, D._REMAT_POLICY, monkeypatch)
    loss0, grads0 = _loss_and_grads(model, OLD_POLICY, monkeypatch)
    np.testing.assert_allclose(float(loss), float(loss0), rtol=1e-6)
    assert set(grads) == set(grads0)
    for k in grads:
        np.testing.assert_allclose(np.asarray(grads[k]),
                                   np.asarray(grads0[k]), rtol=1e-5,
                                   atol=1e-7, err_msg=k)


# -- the counter ---------------------------------------------------------

def _hand_count(preset):
    """{name: bytes} a step of the tiny preset keeps, from shapes."""
    kinds = (["mla+dense", "mla+moe", "mla+moe"] if preset == "glm" else
             ["gqa_full+moe_pre"] + ["gqa_window+moe_pre"] * 3)
    want = collections.Counter()
    for kind in kinds:
        for name, width in KEPT[kind].items():
            want[name] += B * (T - 1) * width * F32
    return dict(want)


@pytest.mark.parametrize("preset", ["glm", "smallthinker"])
def test_bytes_kept_reach_the_monitor_and_the_compile_record(preset):
    """A train step's trace adds to `remat.saved_bytes.<name>` what its
    layers keep, lead and scanned layers alike, and the step's compile
    record carries the same dict."""
    from paddle_tpu import optimizer as opt
    from paddle_tpu.jit import TrainStep
    import paddle_tpu.nn.functional as Fn
    model = _model(preset)
    step = TrainStep(
        model, lambda logits, y: Fn.cross_entropy(
            logits.reshape([-1, logits.shape[-1]]), y.reshape([-1])),
        opt.AdamW(learning_rate=1e-4, parameters=model.parameters()))
    ids = np.random.RandomState(2).randint(0, 256, (B, T)).astype(np.int32)
    before = monitor.counters_under("remat.saved_bytes.")
    step(paddle.to_tensor(ids[:, :-1]), paddle.to_tensor(ids[:, 1:]))
    after = monitor.counters_under("remat.saved_bytes.")
    added = {k: v - before.get(k, 0) for k, v in after.items()
             if v != before.get(k, 0)}
    want = _hand_count(preset)
    assert added == want
    rec = [r for r in compile_observatory.ledger()
           if r["tag"] == "train.step"][-1]
    assert rec["remat_saved_bytes"] == want
