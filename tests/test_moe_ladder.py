"""The ladder of static row counts under the dropless expert layer's
sorted buffer (incubate/moe.py buffer_rungs, dropless_experts): the rungs
come from shapes alone, every rung gives the worst case's bits, the rows
past the groups — undefined on the chip — reach nothing, the fifth
counter names the rung taken, and crossing a rung compiles nothing."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.incubate import moe as M

# tests/test_decoder_moe.py's tiny decoder: 8 routed experts, 2 held, top-2
TINY = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=3, num_attention_heads=4, q_lora_rank=32,
            kv_lora_rank=16, qk_nope_head_dim=24, qk_rope_head_dim=8,
            v_head_dim=32, moe_intermediate_size=48, n_routed_experts=2,
            router_experts=8, local_expert_start=2, num_experts_per_tok=2)
HP = {"lr": 1e-4, "beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8,
      "weight_decay": 0.01}

# the bare layer: 256 tokens, top-2 of 16 experts, 2 held from index 2: 64 held
# assignments under uniform routing, rungs of 128 and 512 rows
N, D, F, K, E, FIRST, HELD = 256, 16, 24, 2, 16, 2, 2
LOW, WORST = 128, 512


@pytest.mark.parametrize("shape,want", [
    ((16384, 6, 8, 64), (15360, 98304)),    # smallthinker-21b-ep8's cell
    ((8192, 4, 8, 64), (5120, 32768)),      # glm-4.7-flash-ep8's cell
    ((8192, 4, 64, 64), (32768,)),          # every expert held
    ((48, 2, 3, 8), (96,)),                 # a tile is no smaller
    ((64, 2, 1, 64), (64,)),                # one held expert, k > n_local
    ((N, K, HELD, E), (LOW, WORST)),        # the bare layer of this file
], ids=["smallthinker", "glm", "all_held", "tiny", "one_held", "this_file"])
def test_buffer_rungs_from_shapes_alone(shape, want):
    rungs = M.buffer_rungs(*shape)
    n, k, n_local, _ = shape
    assert rungs == want
    assert list(rungs) == sorted(set(rungs))
    assert rungs[-1] == n * min(k, n_local)
    assert all(r % M.ROW_TILE == 0 for r in rungs[:-1])


def operands(seed=0, dtype=jnp.float32):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (N, D), dtype)
    w = jax.random.uniform(jax.random.fold_in(key, 1), (N, K), jnp.float32,
                           0.2, 1.0)
    wg, wu, wd = ((0.3 * jax.random.normal(jax.random.fold_in(key, i), s)
                   ).astype(dtype)
                  for i, s in ((2, (HELD, D, F)), (3, (HELD, D, F)),
                               (4, (HELD, F, D))))
    return x, w, wg, wu, wd


def routing(n_held, seed=0):
    """chosen [N, K] with exactly n_held assignments on the held experts
    (2, 3), spread over tokens and places, the others elsewhere."""
    rng = np.random.RandomState(seed)
    others = [e for e in range(E) if not FIRST <= e < FIRST + HELD]
    chosen = np.stack([rng.choice(others, K, replace=False)
                       for _ in range(N)])
    flat = chosen.reshape(-1)
    where = rng.permutation(N * K)[:n_held] if n_held < N * K \
        else np.arange(N * K)
    for a in where:
        # the token's two places hold distinct experts
        other = flat[a ^ 1]
        flat[a] = FIRST + 1 if other == FIRST else FIRST
    chosen = flat.reshape(N, K)
    assert ((chosen >= FIRST) & (chosen < FIRST + HELD)).sum() == n_held
    assert (chosen[:, 0] != chosen[:, 1]).all()
    return jnp.asarray(chosen, jnp.int32)


def value_and_grads(fn, ops, cot):
    """(y, dx, dw, dgate, dup, ddown) of fn(*ops) under the cotangent,
    as one compiled program (op by op the compiler fuses nothing, and a
    switch's branches are compiled: the last bits would differ for that)."""
    def run(cot, *ops):
        y, pull = jax.vjp(fn, *ops)
        return (y,) + pull(cot)
    return jax.jit(run)(cot, *ops)


def on_the_ladder(chosen):
    return lambda *ops: M.dropless_experts(
        ops[0], chosen, ops[1], *ops[2:], FIRST, jax.nn.silu, E)


def on_the_worst_case(chosen):
    plan = M.dispatch_plan(chosen, FIRST, HELD)
    return lambda *ops: M._experts_at(WORST, jax.nn.silu, plan, *ops)


@pytest.mark.parametrize("n_held", [0, LOW - 1, LOW, LOW + 1, WORST],
                         ids=["none", "one_under", "exactly", "one_over",
                              "all_held"])
def test_every_rung_gives_the_worst_cases_bits(n_held):
    chosen = routing(n_held)
    ops = operands()
    cot = jax.random.normal(jax.random.PRNGKey(9), (N, D), jnp.float32)
    y, counters = on_the_ladder(chosen)(*ops)
    rows = LOW if n_held <= LOW else WORST
    assert counters.shape == (len(M.STEP_COUNTERS),) == (5,)
    got = dict(zip(M.STEP_COUNTERS, map(int, counters)))
    assert got["moe.assignments"] == N * K
    assert got["moe.local_assignments"] == n_held
    assert got["moe.dropped"] == 0
    assert got["moe.buffer_rows"] == rows
    ladder = value_and_grads(lambda *a: on_the_ladder(chosen)(*a)[0], ops,
                             cot)
    worst = value_and_grads(on_the_worst_case(chosen), ops, cot)
    for name, a, b in zip(("y", "dx", "dw", "dgate", "dup", "ddown"),
                          ladder, worst):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)
    assert np.isfinite(np.asarray(y)).all()
    assert (n_held == 0) == (not np.asarray(y).any())


def primitives(jaxpr, found=None):
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        found.append(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            primitives(sub, found)
    return found


def test_one_switch_on_a_ladder_and_none_where_every_expert_is_held():
    ops = operands()
    chosen = routing(40)
    held_here = jax.make_jaxpr(lambda *a: on_the_ladder(chosen)(*a)[0])(*ops)
    assert primitives(held_here.jaxpr).count("cond") == 1
    wg, wu, wd = (jnp.concatenate([a] * (E // HELD)) for a in ops[2:])
    every = jax.make_jaxpr(lambda x, w: M.dropless_experts(
        x, chosen, w, wg, wu, wd, 0, jax.nn.silu, E)[0])(*ops[:2])
    assert "cond" not in primitives(every.jaxpr)
    # a caller that does not say how many experts there are holds them all
    plain = jax.make_jaxpr(lambda *a: M.dropless_experts(
        a[0], chosen, a[1], *a[2:], FIRST)[0])(*ops)
    assert "cond" not in primitives(plain.jaxpr)


def layers_grads(wrap, chosen_by_layer, ops):
    """Two expert layers with a residual, run as models/decoder.py runs
    them — each under jax.checkpoint, or one lax.scan over the stacked
    layers with the checkpoint inside: (loss, gradients, counters)."""
    x, w, wg, wu, wd = ops
    stacked = tuple(jnp.stack([a, 0.5 * a]) for a in (wg, wu, wd))
    chosen = jnp.stack(chosen_by_layer)

    def layer(h, per):
        c, g, u, d = per
        y, counters = M.dropless_experts(h, c, w, g, u, d, FIRST,
                                         jax.nn.silu, E)
        return h + y, counters

    def loss(x, stacked):
        if wrap == "scan":
            h, counters = jax.lax.scan(
                jax.checkpoint(layer, prevent_cse=False), x,
                (chosen, *stacked))
            counters = counters.sum(0)
        else:
            h, counters = x, 0
            for i in range(2):
                h, c = jax.checkpoint(layer)(
                    h, (chosen[i], *(s[i] for s in stacked)))
                counters = counters + c
        return (h ** 2).sum(), counters

    (l, counters), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True))(x, stacked)
    return l, grads, counters


@pytest.mark.parametrize("wrap", ["checkpoint", "scan"])
def test_under_checkpoint_and_scan_as_the_decoder_runs_it(wrap, monkeypatch):
    """One layer on the low rung, one past it."""
    routes = [routing(60, seed=1), routing(300, seed=2)]
    ops = operands(seed=3)
    l, grads, counters = layers_grads(wrap, routes, ops)
    assert int(counters[4]) == LOW + WORST and int(counters[3]) == 0
    assert int(counters[1]) == 360
    monkeypatch.setattr(M, "buffer_rungs",
                        lambda n, k, n_local, e: (n * min(k, n_local),))
    l0, grads0, counters0 = layers_grads(wrap, routes, ops)
    assert int(counters0[4]) == 2 * WORST
    np.testing.assert_array_equal(np.asarray(l), np.asarray(l0))
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(grads0)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def spoiled(sound, fill):
    """The grouped product with `fill` in every row past the groups, in the
    product and in the gradient of the rows: NaN is the chip at its worst,
    0.0 what the CPU gives by itself (the same program but for a constant,
    so the two can be held to the same bits)."""
    def spoil(a, sizes):
        return jnp.where((jnp.arange(a.shape[0]) < sizes.sum())[:, None], a,
                         fill)

    @jax.custom_vjp
    def mm(rows, w, sizes):
        return spoil(sound(rows, w, sizes), sizes)

    def fwd(rows, w, sizes):
        out, pull = jax.vjp(lambda r, v: sound(r, v, sizes), rows, w)
        return spoil(out, sizes), (pull, sizes)

    def bwd(res, g):
        pull, sizes = res
        drows, dw = pull(g)
        return spoil(drows, sizes), dw, None

    mm.defvjp(fwd, bwd)
    return mm


@pytest.mark.parametrize("n_held", [100, 300], ids=["low_rung", "top_rung"])
def test_rows_past_the_groups_reach_nothing(n_held, monkeypatch):
    chosen = routing(n_held, seed=4)
    ops = operands(seed=5)
    cot = jax.random.normal(jax.random.PRNGKey(8), (N, D), jnp.float32)
    fn = lambda *a: on_the_ladder(chosen)(*a)[0]
    sound = M.grouped_matmul
    monkeypatch.setattr(M, "grouped_matmul", spoiled(sound, 0.0))
    want = value_and_grads(fn, ops, cot)
    monkeypatch.setattr(M, "grouped_matmul", spoiled(sound, jnp.nan))
    # the poison is there: the product's last row is NaN
    sizes = M.dispatch_plan(chosen, FIRST, HELD)[0]
    assert np.isnan(np.asarray(M.grouped_matmul(
        jnp.ones((WORST, D)), ops[2], sizes))[-1]).all()
    got = value_and_grads(fn, ops, cot)
    for name, a, b in zip(("y", "dx", "dw", "dgate", "dup", "ddown"),
                          got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)


def test_the_layer_names_the_fifth_counter():
    assert M.STEP_COUNTERS[4] == "moe.buffer_rows"
    layer = M.DroplessMoE(D, F, E, K, local_experts=range(FIRST,
                                                          FIRST + HELD))
    assert layer.step_counter_names == M.STEP_COUNTERS
    layer(paddle.to_tensor(operands()[0]))          # eager: switch works
    got = dict(zip(M.STEP_COUNTERS, map(int, layer._step_counters)))
    assert got["moe.buffer_rows"] in (LOW, WORST)
    assert got["moe.buffer_rows"] >= got["moe.local_assignments"]
    assert got["moe.dropped"] == 0


def test_crossing_a_rung_between_steps_compiles_nothing():
    """A tiny decoder of glm-4.7-flash-ep8's form, 256 tokens a step: the
    selection bias, a buffer and so data of the compiled step, sends every
    token to the two held experts from the second step on."""
    from benchmarks.lib import program as P
    from benchmarks.lib import train as T
    from paddle_tpu.profiler import monitor
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "glm-4.7-flash-ep8.json")) as f:
        config = json.load(f)
    config.update(TINY, dtype="float32")
    config["program"]["kwargs"] = dict(TINY)
    model = P.build_model(config)
    step = T.build_step({"batch": 2, "seq": 128, "optimizer": HP}, model)
    moes = [l for l in model.sublayers() if isinstance(l, M.DroplessMoE)]
    n, k = 2 * 128, TINY["num_experts_per_tok"]
    first, held = TINY["local_expert_start"], TINY["n_routed_experts"]
    low, worst = M.buffer_rungs(n, k, held, TINY["router_experts"])
    ids = np.random.RandomState(0).randint(0, TINY["vocab_size"],
                                           (2, 129)).astype(np.int32)
    x, y = paddle.to_tensor(ids[:, :-1]), paddle.to_tensor(ids[:, 1:])

    def rows_of_a_step():
        monitor.reset_metrics()
        step(x, y).item()
        step.flush_step_counters()
        snap = monitor.metrics_snapshot()
        assert snap["moe.dropped"] == 0
        return snap["moe.buffer_rows"], snap["moe.local_assignments"]

    rows, local = rows_of_a_step()
    assert rows == len(moes) * low and local < rows
    bias = np.zeros(TINY["router_experts"], np.float32)
    bias[first:first + held] = 10.0
    names = [n for n in step.buffers if n.endswith("e_score_correction_bias")]
    assert len(names) == len(moes)
    for name in names:              # the step holds the buffers it was built on
        step.buffers[name] = jnp.asarray(bias)
    rows, local = rows_of_a_step()
    assert rows == local == len(moes) * worst
    assert step.retraces == 1
