"""The correctness readings hold no whole extra copy of the weights (PR
36). On the tiny copies of tests/tiny.py, with the fused and the tree
epilogue: the program's readings and the reference's equal the old
all-at-once computation leaf by leaf, the readings call returns a scalar
(a row, for a leaf stacked by layer) a leaf, and the reference's steps
hold w, m, v and one gradient. At the cells' own sizes, from shapes
alone, compiled for a described v5e: the readings call holds at most
twice the largest leaf's float32 bytes beside the state it reads (at the
tiny sizes neither the chip's compiler, which lays the generator's rounds
out apart there, nor the CPU's, which keeps every intermediate, says what
the cells' call holds)."""
import gc
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from benchmarks.lib import memory as M
from benchmarks.lib import program as P
from benchmarks.lib import reftrain, train
from benchmarks.references.common import (make_weights, mean_xent,
                                          seeded_leaf, weights_from_seed)
from benchmarks.tests import tiny
from benchmarks.tests.test_rehearsal import TRAIN

SEED = 3000000029
REL = 1e-6


# ---- the old computations, as the parent commit made them -------------
def old_leaf_norms(tree):
    @jax.jit
    def norms(t):
        return {k: jnp.sqrt(jnp.sum(
            jnp.square(v.astype(jnp.float32)),
            axis=tuple(range(1, v.ndim)) if ".h.*." in k else None))
            for k, v in t.items()}
    out = {}
    for k, v in jax.device_get(norms(tree)).items():
        if ".h.*." in k:
            for i, x in enumerate(np.asarray(v)):
                out[k.replace(".h.*.", f".h.{i}.")] = float(x)
        else:
            out[k] = float(v)
    return out


def old_reference_train(ref, config, seed, batches, hp, micro):
    spec = ref.param_spec(config)
    w0 = weights_from_seed(spec, seed, config["dtype"])

    @jax.jit
    def loss_and_grad(w, toks):
        def loss(w):
            return mean_xent(ref.forward(w, config, toks[:, :-1], "f32"),
                             toks[:, 1:])
        return jax.value_and_grad(loss)(w)

    upd = jax.jit(lambda w, g, m, v, t: reftrain.adamw(w, g, m, v, t, hp),
                  static_argnums=(4,), donate_argnums=(0, 2, 3))
    w = jax.tree.map(jnp.copy, w0)
    m = jax.tree.map(jnp.zeros_like, w0)
    v = jax.tree.map(jnp.zeros_like, w0)
    out = {"losses": []}
    for t, toks in enumerate(batches, 1):
        toks = np.asarray(toks)
        parts = [toks[i:i + micro] for i in range(0, toks.shape[0], micro)]
        loss, grads = 0.0, None
        for p in parts:
            l, g = loss_and_grad(w, jnp.asarray(p, jnp.int32))
            share = p.shape[0] / toks.shape[0]
            loss += float(l) * share
            g = jax.tree.map(lambda a: a * share, g)
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        out["losses"].append(loss)
        if t == 1:
            out["grad_norms"] = old_leaf_norms(grads)
        w, m, v = upd(w, grads, m, v, t)
    out["change_norms"] = old_leaf_norms(
        jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))(w, w0))
    return out


def assert_close(new, old):
    assert set(new) == set(old)
    for k in old:
        assert abs(new[k] - old[k]) <= REL * max(abs(old[k]), 1e-30), \
            (k, new[k], old[k])


# ---- the program's side -----------------------------------------------
def driven(monkeypatch, cell_name, fused):
    """A tiny cell's step through the runner's three checked steps, with
    the new and the old readings of each."""
    from paddle_tpu import optimizer as opt
    from paddle_tpu.jit import TrainStep
    tiny.patch(monkeypatch)
    cell, config, _, _ = P.load_cell(cell_name)
    spec = P.reference_of(config).param_spec(config)
    model = P.build_model(config)
    P.install_weights(model, weights_from_seed(spec, SEED, config["dtype"]))
    hp = cell["optimizer"]
    step = TrainStep(model, train.loss_fn, opt.AdamW(
        learning_rate=hp["lr"], beta1=hp["beta1"], beta2=hp["beta2"],
        epsilon=hp["epsilon"], weight_decay=hp["weight_decay"],
        parameters=model.parameters(), multi_precision=True),
        fused_update=None if fused else False)
    assert isinstance(step.opt_state, dict) is not fused
    it = train.feed(train.make_batches(SEED, config["vocab_size"],
                                       cell["batch"], cell["seq"],
                                       train.CHECK_STEPS))
    phases = M.Phases(jax.devices()[:1])
    phases.start("setup")
    got = {}
    for i in range(train.CHECK_STEPS):
        float(step(*next(it)).item())
        if i == 0:
            got["first_moment"] = (
                train.state_norms(step.opt_state, spec,
                                  lambda s: s["state"][0], phases,
                                  "readings.first_moment"),
                old_leaf_norms({k: s["state"][0]
                                for k, s in step.opt_state.items()}))
    it.close()
    w0 = weights_from_seed(spec, SEED, config["dtype"])
    masters = {k: s["master"] for k, s in step.opt_state.items()}
    got["change"] = (
        train.program_readings(step, (spec, SEED, config["dtype"]), [],
                               {"beta1": 0.9}, {}, phases)["change_norms"],
        old_leaf_norms(jax.jit(lambda m, w: {
            k: m[k] - P.leaf_of(w, k) for k in m})(masters, w0)))
    return step, spec, config, phases, got


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "tree"])
@pytest.mark.parametrize("cell", TRAIN)
def test_program_readings_equal_old(monkeypatch, cell, fused):
    _, _, _, phases, got = driven(monkeypatch, cell, fused)
    for new, old in got.values():
        assert_close(new, old)
    readings = phases.out["setup"]["readings"]
    assert set(readings) == {"readings.first_moment", "readings.change"}


def test_seeded_leaf_flat_is_the_leaf_in_one_row():
    """The fused path draws the seeded leaves flat: the same values."""
    spec = {"a": ((3, 5, 7), "normal"), "b": ((4, 6), "dt_bias"),
            "c": ((9,), "sign"), "d": ((2, 8), "a_log"),
            "e": ((3, 4), "ones")}
    whole = make_weights(spec, 77, jnp.bfloat16)
    key = jax.random.PRNGKey(77)
    for i, (k, (shape, kind)) in enumerate(sorted(spec.items())):
        row = seeded_leaf(key, i, shape, kind, jnp.bfloat16, flat=True)
        np.testing.assert_array_equal(np.asarray(row),
                                      np.asarray(whole[k]).reshape(-1))


@pytest.fixture(scope="module")
def v5e():
    """Device 0 of a described v5e, with the persistent compile cache
    off around it (as tests/test_chip_compile.py does)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "tree"])
@pytest.mark.parametrize("cell", TRAIN)
def test_readings_call_returns_norms_alone(monkeypatch, cell, fused):
    step, spec, config, _, _ = driven(monkeypatch, cell, fused)
    regen = lambda s, flat: reftrain.regenerated(spec, s, config["dtype"],
                                                 flat)
    for seeded, pick in ((None, lambda s: s["state"][0]),
                         (regen, lambda s: s["master"])):
        held, norms = train.readings_call(step.opt_state, spec, pick,
                                          seeded)
        out = jax.eval_shape(norms, held, jnp.int32(0))
        assert set(out) == set(spec)
        for k, (shape, _) in spec.items():
            want = (shape[0],) if reftrain.STACKED in k else ()
            assert out[k].shape == want and out[k].dtype == jnp.float32


def held_state(spec, device, fused):
    """The optimizer state a step holds for spec's leaves (float32
    master and two moments a bf16 parameter) as shapes on `device`: the
    tree epilogue's dict, or the fused path's view over the flat stores
    the program lays out."""
    from paddle_tpu.ops.pallas import fused_update as fu
    f32 = lambda shape: jax.ShapeDtypeStruct(tuple(shape), jnp.float32,
                                             sharding=device)
    leaves = []
    for k, (shape, _) in spec.items():
        if reftrain.STACKED in k:
            leaves += [(k.replace(reftrain.STACKED, f".h.{i}."), shape[1:])
                       for i in range(shape[0])]
        else:
            leaves.append((k, shape))
    if not fused:
        return {k: {"master": f32(s), "state": (f32(s), f32(s))}
                for k, s in leaves}
    lay = fu.BucketLayout([(k, s, jnp.bfloat16) for k, s in leaves])
    store = {key: f32(lay.bucket_shape(key)) for key in lay.buckets}
    return fu.LeafStateView(fu.FusedEpilogue(lay, {"n_moments": 2}),
                            {"moments": (store, dict(store)),
                             "masters": dict(store)})


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "tree"])
@pytest.mark.parametrize("config", [
    "gpt2-medium", "glm-4.7-flash-ep8", "smallthinker-21b-ep8"])
def test_readings_call_memory(v5e, config, fused):
    """The call adds at most twice the largest leaf's float32 bytes,
    and under 0.5 GB, beside the state it reads."""
    config = P.load_json("configs", f"{config}.json")
    spec = P.reference_of(config).param_spec(config)
    state = held_state(spec, v5e, fused)
    seed = jax.ShapeDtypeStruct((), jnp.int32, sharding=v5e)
    largest = max(4 * int(np.prod(shape)) for shape, _ in spec.values())
    regen = lambda s, flat: reftrain.regenerated(spec, s, config["dtype"],
                                                 flat)
    for seeded, pick in ((None, lambda s: s["state"][0]),
                         (regen, lambda s: s["master"])):
        held, norms = train.readings_call(state, spec, pick, seeded)
        a = M.analysis(jax.jit(norms).lower(held, seed).compile())
        assert a["temp_bytes"] + a["output_bytes"] <= min(
            2 * largest, 0.5e9), (seeded, a, largest)


# ---- the reference's side ---------------------------------------------
def tiny_cell(monkeypatch, cell_name):
    tiny.patch(monkeypatch)
    cell, config, _, _ = P.load_cell(cell_name)
    batches = train.make_batches(SEED, config["vocab_size"], cell["batch"],
                                 cell["seq"], train.CHECK_STEPS)
    return cell, config, P.reference_of(config), batches


@pytest.mark.parametrize("split", [True, False], ids=["split", "whole"])
@pytest.mark.parametrize("cell", TRAIN)
def test_reference_readings_equal_old(monkeypatch, cell, split):
    cell, config, ref, batches = tiny_cell(monkeypatch, cell)
    micro = 1 if split else cell["batch"]
    new = reftrain.reference_train(ref, config, SEED, batches,
                                   cell["optimizer"], micro=micro)
    old = old_reference_train(ref, config, SEED, batches,
                              cell["optimizer"], micro)
    for key in ("grad_norms", "change_norms"):
        assert_close(new[key], old[key])
    assert_close(dict(enumerate(new["losses"])),
                 dict(enumerate(old["losses"])))


class LiveBytes(M.Phases):
    """Phases that read what is held as the bytes of every live array
    (the CPU's allocator keeps no statistics)."""

    def bytes_in_use(self):
        gc.collect()
        return sum(a.nbytes for a in jax.live_arrays())


@pytest.mark.parametrize("split", [True, False], ids=["split", "whole"])
@pytest.mark.parametrize("cell", TRAIN)
def test_reference_holds_one_gradient(monkeypatch, cell, split):
    cell, config, ref, batches = tiny_cell(monkeypatch, cell)
    spec = ref.param_spec(config)
    tree = 4 * sum(int(np.prod(shape)) for shape, _ in spec.values())
    phases = LiveBytes(jax.devices()[:1])
    phases.start("reference")
    reftrain.reference_train(ref, config, SEED, batches, cell["optimizer"],
                             micro=1 if split else cell["batch"],
                             phases=phases)
    held = (max(m[1] for m in phases.now["marks"])
            - phases.now["bytes_in_use_at_start"])
    # w, m, v and one gradient, and the step's tokens and loss beside
    assert 4 * tree <= held <= 4 * tree + 64 * 1024, (held, tree)
    assert "reference.change" in phases.now["readings"]
