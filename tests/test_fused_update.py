"""Fused multi-tensor update epilogue (ops/pallas/fused_update.py).

The contract under test: TrainStep/HybridTrainStep with the fused
epilogue (dtype-bucketed flat buffers, two Pallas passes, interpret mode
on CPU) are NUMERICALLY EQUAL to the per-leaf tree path — bit-for-bit
where only elementwise math is involved (clip off), within
reduction-order ulps where the global norm enters (clip on) — across
Adam/AdamW/Momentum/SGD, bf16 master weights, found_inf-skip semantics,
tensor lr, and the accumulate/run_steps program flavors. Plus: the
escape hatch (PADDLE_TPU_FUSED_UPDATE=0) keeps the tree path alive,
unsupported configs fall back silently, warm-pipeline coverage adds
zero executables, and the step record carries the epilogue cost split.
"""
import json
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import optimizer as opt
from paddle_tpu.amp import GradScaler
from paddle_tpu.jit import TrainStep
from paddle_tpu.nn.clip import (ClipGradByGlobalNorm, ClipGradByNorm,
                                ClipGradByValue)


def _loss_fn(out, y):
    return nn.functional.cross_entropy(out, y)


def _model(seed=0, bf16=False):
    paddle.seed(seed)
    m = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    if bf16:
        m.bfloat16()
    return m


def _batch(bf16=False):
    x = np.random.RandomState(0).randn(4, 8).astype(np.float32)
    xt = paddle.to_tensor(x)
    if bf16:
        xt = xt.astype("bfloat16")
    return xt, paddle.to_tensor(np.array([0, 1, 2, 3], np.int64))


def _pair(opt_factory, seed=0, bf16=False, scaler=None, **step_kw):
    """(fused_step, tree_step) over identically-seeded models."""
    steps = []
    for fused in (True, False):
        m = _model(seed, bf16)
        o = opt_factory(m)
        sc = None
        if scaler is not None:
            sc = GradScaler(**scaler)
        steps.append(TrainStep(m, _loss_fn, o, scaler=sc,
                               fused_update=fused, **step_kw))
    assert steps[0]._fused is not None, "fused path did not engage"
    assert steps[1]._fused is None
    return steps


def _assert_state_equal(a, b, exact=True, rtol=2e-6, atol=1e-7):
    """params + opt_state of two TrainSteps (tree VIEWS on both)."""
    pa, pb = a.params, b.params
    assert set(pa) == set(pb)
    for k in pa:
        x, y = np.asarray(pa[k], np.float32), np.asarray(pb[k],
                                                         np.float32)
        if exact:
            np.testing.assert_array_equal(x, y, err_msg=f"param {k}")
        else:
            np.testing.assert_allclose(x, y, rtol=rtol, atol=atol,
                                       err_msg=f"param {k}")
    # the property differs by path (test_opt_state_as_users_see_it):
    # a dict on the tree path, a lazy Mapping on the fused one. As dicts
    # they are one structure
    assert list(a.opt_state) == list(b.opt_state)
    sa, sb = dict(a.opt_state), dict(b.opt_state)
    assert jax.tree.structure(sa) == jax.tree.structure(sb)
    for la, lb in zip(jax.tree.leaves(sa), jax.tree.leaves(sb)):
        x, y = np.asarray(la, np.float32), np.asarray(lb, np.float32)
        if exact:
            np.testing.assert_array_equal(x, y)
        else:
            np.testing.assert_allclose(x, y, rtol=rtol, atol=atol)


# ------------------------------------------------------- path selection
def test_fused_on_by_default_and_escape_hatch(monkeypatch):
    m = _model()
    st = TrainStep(m, _loss_fn,
                   opt.AdamW(learning_rate=1e-3,
                             parameters=m.parameters()))
    assert st._fused is not None
    monkeypatch.setenv("PADDLE_TPU_FUSED_UPDATE", "0")
    st2 = TrainStep(m, _loss_fn,
                    opt.AdamW(learning_rate=1e-3,
                              parameters=m.parameters()))
    assert st2._fused is None  # escape hatch keeps the tree path alive


@pytest.mark.parametrize("make_opt", [
    lambda m: opt.LarsMomentum(learning_rate=1e-3,
                               parameters=m.parameters()),
    lambda m: opt.RMSProp(learning_rate=1e-3,
                          parameters=m.parameters()),
    lambda m: opt.AdamW(learning_rate=1e-3, parameters=m.parameters(),
                        grad_clip=ClipGradByNorm(1.0)),
])
def test_unsupported_configs_fall_back_to_tree(make_opt):
    m = _model()
    st = TrainStep(m, _loss_fn, make_opt(m))
    assert st._fused is None
    x, y = _batch()
    assert np.isfinite(float(st(x, y).item()))


def test_stochastic_rounding_falls_back():
    m = _model()
    o = opt.AdamW(learning_rate=1e-3, parameters=m.parameters())
    o._stochastic_rounding = True
    assert TrainStep(m, _loss_fn, o)._fused is None


# -------------------------------------------------- numerical equality
@pytest.mark.parametrize("make_opt", [
    lambda m: opt.AdamW(learning_rate=1e-3, parameters=m.parameters()),
    lambda m: opt.Adam(learning_rate=1e-3, parameters=m.parameters()),
    lambda m: opt.Momentum(learning_rate=1e-2, momentum=0.9,
                           use_nesterov=True,
                           parameters=m.parameters()),
    lambda m: opt.SGD(learning_rate=1e-2, parameters=m.parameters()),
])
def test_fused_equals_tree_bitwise_no_clip(make_opt):
    fused, tree = _pair(make_opt)
    x, y = _batch()
    for _ in range(4):
        lf = float(fused(x, y).item())
        lt = float(tree(x, y).item())
        assert lf == lt
    _assert_state_equal(fused, tree, exact=True)


def test_fused_equals_tree_with_global_clip_and_scaler():
    fused, tree = _pair(
        lambda m: opt.AdamW(learning_rate=1e-3,
                            parameters=m.parameters(),
                            grad_clip=ClipGradByGlobalNorm(0.25)),
        scaler={"init_loss_scaling": 2.0 ** 10})
    x, y = _batch()
    for _ in range(4):
        lf, lt = float(fused(x, y).item()), float(tree(x, y).item())
        assert lf == pytest.approx(lt, rel=1e-6)
    # clip factor comes from the one shared norm: reduction order may
    # differ by ulps, everything downstream stays within float32 noise
    _assert_state_equal(fused, tree, exact=False)
    assert float(fused.scaler_state["scale"]) == \
        float(tree.scaler_state["scale"])


def test_fused_equals_tree_clip_by_value():
    fused, tree = _pair(
        lambda m: opt.Adam(learning_rate=1e-3,
                           parameters=m.parameters(),
                           grad_clip=ClipGradByValue(0.01)))
    x, y = _batch()
    for _ in range(3):
        assert float(fused(x, y).item()) == float(tree(x, y).item())
    _assert_state_equal(fused, tree, exact=True)


def test_fused_bf16_master_weights_bitwise():
    fused, tree = _pair(
        lambda m: opt.AdamW(learning_rate=0.05,
                            parameters=m.parameters(),
                            multi_precision=True),
        bf16=True)
    x, y = _batch(bf16=True)
    for _ in range(5):
        assert float(fused(x, y).item()) == float(tree(x, y).item())
    # masters (f32) and the bf16 shadow params must agree BITWISE: the
    # downcast is the numerically sharpest edge of the kernel
    _assert_state_equal(fused, tree, exact=True)
    leaf = fused.opt_state["0.weight"]
    assert isinstance(leaf, dict) and "master" in leaf
    assert leaf["master"].dtype == jnp.float32
    assert fused.params["0.weight"].dtype == jnp.bfloat16


def test_opt_state_as_users_see_it():
    """TrainStep.opt_state by path: a dict on the tree path; on the fused
    path a read-only Mapping that slices each leaf out when it is read.
    dict() of either is one plain tree; the Mapping is a pytree node of
    its own, so it does not map against a dict tree."""
    import collections.abc
    fused, tree = _pair(
        lambda m: opt.AdamW(learning_rate=0.05,
                            parameters=m.parameters(),
                            multi_precision=True),
        bf16=True)
    x, y = _batch(bf16=True)
    fused(x, y), tree(x, y)
    assert type(tree.opt_state) is dict
    view = fused.opt_state
    assert isinstance(view, collections.abc.Mapping)
    assert not isinstance(view, dict)
    assert list(view) == list(tree.opt_state) and len(view) == len(
        tree.opt_state)
    assert "0.weight" in view and "no.such.leaf" not in view
    with pytest.raises(KeyError):
        view["no.such.leaf"]
    with pytest.raises(TypeError):
        view["0.weight"] = None
    # a leaf is sliced anew at each read, equal to the tree path's
    for name, leaf in view.items():
        assert leaf["master"] is not view[name]["master"]
        np.testing.assert_array_equal(
            np.asarray(leaf["master"]),
            np.asarray(tree.opt_state[name]["master"]))
    plain = dict(view)
    assert jax.tree.structure(plain) == jax.tree.structure(tree.opt_state)
    # as a pytree: the dict's leaves under a node of its own
    assert len(jax.tree.leaves(view)) == len(jax.tree.leaves(plain))
    assert type(jax.tree.map(lambda a: a, view)) is dict
    assert jax.tree.structure(view) != jax.tree.structure(plain)
    with pytest.raises(ValueError):
        jax.tree.map(lambda a, b: a, view, plain)
    # checkpoint code takes dict() first (tree_state)
    assert type(fused.tree_state()["opt_state"]) is dict


def test_found_inf_skips_update_and_backs_off_scale():
    fused, tree = _pair(
        lambda m: opt.AdamW(learning_rate=1e-3,
                            parameters=m.parameters()),
        scaler={"init_loss_scaling": 2.0 ** 15,
                "decr_every_n_nan_or_inf": 1})
    x, y = _batch()
    bad = paddle.to_tensor(np.full((4, 8), np.inf, np.float32))
    for st in (fused, tree):
        before = np.asarray(st.params["0.weight"]).copy()
        m_before = np.asarray(jax.tree.leaves(st.opt_state)[0]).copy()
        st(bad, y)
        np.testing.assert_array_equal(
            before, np.asarray(st.params["0.weight"]))
        np.testing.assert_array_equal(
            m_before, np.asarray(jax.tree.leaves(st.opt_state)[0]))
        assert float(st.scaler_state["scale"]) == 2.0 ** 14
    # both recover alike on a good batch (to rounding: under jax 0.9.0
    # XLA:CPU fuses the unscale into the two epilogues differently, and
    # one weight differs by an f32 ulp)
    assert float(fused(x, y).item()) == \
        pytest.approx(float(tree(x, y).item()), rel=1e-6)
    _assert_state_equal(fused, tree, exact=False)


def test_nan_without_scaler_still_updates_like_tree():
    """No GradScaler -> no found_inf skip: a NaN batch must poison the
    params on BOTH paths (the fused kernel must not invent a skip)."""
    fused, tree = _pair(
        lambda m: opt.SGD(learning_rate=1e-2,
                          parameters=m.parameters()))
    y = _batch()[1]
    bad = np.random.RandomState(0).randn(4, 8).astype(np.float32)
    bad[0, 0] = np.nan
    bad_t = paddle.to_tensor(bad)
    fused(bad_t, y), tree(bad_t, y)
    wf = np.asarray(fused.params["0.weight"])
    wt = np.asarray(tree.params["0.weight"])
    assert np.isnan(wf).any() and np.isnan(wt).any()
    np.testing.assert_array_equal(np.isnan(wf), np.isnan(wt))


def test_tensor_lr_schedule_no_retrace_and_equal():
    """lr is a traced argument: changing it between steps must not
    recompile, and the fused kernels must consume the live value."""
    fused, tree = _pair(
        lambda m: opt.AdamW(learning_rate=1e-3,
                            parameters=m.parameters()))
    x, y = _batch()
    for lr in (1e-3, 5e-4, 2e-3):
        fused.optimizer.set_lr(lr)
        tree.optimizer.set_lr(lr)
        assert float(fused(x, y).item()) == float(tree(x, y).item())
    assert fused.retraces == 1  # lr rides as data, not as a signature
    _assert_state_equal(fused, tree, exact=True)


def test_need_clip_mask_respected_on_both_paths():
    """A Parameter with need_clip=False stays out of the global norm
    AND out of the scaling — identically on fused and tree paths."""
    def make(fused):
        m = _model(3)
        m[2].weight.need_clip = False
        o = opt.AdamW(learning_rate=1e-2, parameters=m.parameters(),
                      grad_clip=ClipGradByGlobalNorm(0.05))
        return TrainStep(m, _loss_fn, o, fused_update=fused)
    fused, tree = make(True), make(False)
    x, y = _batch()
    for _ in range(3):
        assert float(fused(x, y).item()) == \
            pytest.approx(float(tree(x, y).item()), rel=1e-6)
    _assert_state_equal(fused, tree, exact=False)
    # and the mask actually matters: an all-clip run diverges
    allclip = _pair(lambda m: opt.AdamW(
        learning_rate=1e-2, parameters=m.parameters(),
        grad_clip=ClipGradByGlobalNorm(0.05)), seed=3)[0]
    allclip(x, y)
    w_masked = np.asarray(fused.params["2.weight"], np.float32)
    w_all = np.asarray(allclip.params["2.weight"], np.float32)
    assert not np.allclose(w_masked, w_all)


def test_accumulate_path_equality():
    fused, tree = _pair(
        lambda m: opt.AdamW(learning_rate=1e-3,
                            parameters=m.parameters(),
                            grad_clip=ClipGradByGlobalNorm(0.5)),
        scaler={"init_loss_scaling": 2.0 ** 8})
    x, y = _batch()
    k = 3
    xs = paddle.to_tensor(np.stack([np.asarray(x.value)] * k))
    ys = paddle.to_tensor(np.stack([np.asarray(y.value)] * k))
    lf = float(fused.accumulate(k, xs, ys).item())
    lt = float(tree.accumulate(k, xs, ys).item())
    assert lf == pytest.approx(lt, rel=1e-6)
    _assert_state_equal(fused, tree, exact=False)


def test_run_steps_path_equality():
    fused, tree = _pair(
        lambda m: opt.Adam(learning_rate=1e-3,
                           parameters=m.parameters()))
    x, y = _batch()
    lf = fused.run_steps(3, x, y).numpy()
    lt = tree.run_steps(3, x, y).numpy()
    np.testing.assert_array_equal(lf, lt)
    _assert_state_equal(fused, tree, exact=True)


def test_health_vector_equality_and_shared_norm():
    """monitor_health on both paths: same health scalars (the fused
    kernels produce param/update sums as pass-2 side outputs)."""
    def make(fused):
        m = _model(1)
        o = opt.AdamW(learning_rate=1e-3, parameters=m.parameters(),
                      grad_clip=ClipGradByGlobalNorm(1.0))
        return TrainStep(m, _loss_fn, o, monitor_health=True,
                         fused_update=fused)
    fused, tree = make(True), make(False)
    x, y = _batch()
    for _ in range(3):
        float(fused(x, y).item()), float(tree(x, y).item())
    hf, ht = fused.flush_health(), tree.flush_health()
    for k in ("loss", "grad_norm", "param_norm", "update_ratio",
              "found_inf"):
        assert hf[k] == pytest.approx(ht[k], rel=1e-5, abs=1e-7), k


def test_checkpoint_roundtrip_restores_flat_stores(tmp_path):
    """distributed.checkpoint.load_train_state must restore through the
    layout-aware setter: params/opt_state are read-only VIEWS, the
    donated truth on the fused path is the flat stores."""
    from paddle_tpu.distributed.checkpoint import (save_train_state,
                                                   load_train_state)
    x, y = _batch()
    for fused in (True, False):
        src = TrainStep(_model(5), _loss_fn,
                        opt.AdamW(learning_rate=1e-2), fused_update=fused)
        for _ in range(2):
            float(src(x, y).item())
        path = tmp_path / f"ckpt_{fused}"
        save_train_state(src, str(path))
        dst = TrainStep(_model(6), _loss_fn,
                        opt.AdamW(learning_rate=1e-2), fused_update=fused)
        float(dst(x, y).item())  # diverge before restore
        load_train_state(dst, str(path))
        assert dst._step_i == src._step_i
        for k in src.params:
            np.testing.assert_array_equal(np.asarray(src.params[k]),
                                          np.asarray(dst.params[k]))
        for la, lb in zip(jax.tree.leaves(src.opt_state),
                          jax.tree.leaves(dst.opt_state)):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
        # and the restored state actually trains from where src left off
        assert float(src(x, y).item()) == float(dst(x, y).item())


def test_nan_in_need_clip_masked_leaf_trips_health_found_inf():
    """A need_clip=False leaf stays out of the shared norm, but a
    non-finite gradient there must still trip the health observatory's
    found_inf — on both epilogue paths."""
    def make(fused):
        paddle.seed(9)
        m = nn.Linear(4, 1, bias_attr=False)
        m.weight.need_clip = False
        o = opt.SGD(learning_rate=1e-2, parameters=m.parameters(),
                    grad_clip=ClipGradByGlobalNorm(1.0))
        return TrainStep(m, lambda out, t: nn.functional.mse_loss(out, t),
                         o, monitor_health=True, fused_update=fused)
    bad = np.ones((2, 4), np.float32)
    bad[0, 0] = np.nan
    xb = paddle.to_tensor(bad)
    yb = paddle.to_tensor(np.zeros((2, 1), np.float32))
    for fused in (True, False):
        st = make(fused)
        st(xb, yb)
        h = st.flush_health()
        assert h["found_inf"] == 1.0, (fused, h)


def test_pallas_interpret_mode_matches_direct():
    """The Pallas kernel plumbing (grid, BlockSpecs, SMEM accumulators,
    the same row blocking the chip gets) must compute exactly what the
    direct off-TPU path computes — this is what validates the TPU
    kernels from tier-1."""
    from paddle_tpu.ops.pallas.fused_update import (BucketLayout,
                                                    FusedEpilogue)
    rng = np.random.RandomState(3)
    params = {"h.0.w": jnp.asarray(rng.randn(33, 7), jnp.float32),
              "h.1.w": jnp.asarray(rng.randn(33, 7), jnp.float32),
              "b": jnp.asarray(rng.randn(130), jnp.float32)}
    grads = {k: jnp.asarray(rng.randn(*v.shape) * 0.1, v.dtype)
             for k, v in params.items()}
    o = opt.AdamW(learning_rate=0.01)
    lay = BucketLayout([(k, v.shape, v.dtype) for k, v in params.items()])
    scaler = GradScaler(init_loss_scaling=2.0 ** 6)
    clip = ClipGradByGlobalNorm(0.5)
    outs = []
    for interpret in (False, True):
        epi = FusedEpilogue(lay, o.fused_spec(), interpret=interpret)
        assert epi.mode == ("interpret" if interpret else "direct")
        ps, osd = epi.init_stores(params, False)
        gs = lay.pack(grads)
        sstate = scaler.init_jit_state()
        outs.append(jax.jit(
            lambda g, p, s, sc: epi.finish(
                g, p, s, 0.01, 3.0, scaler=scaler, scaler_state=sc,
                clip=clip, with_stats=True))(gs, ps, osd, sstate))
    (p_a, o_a, s_a, aux_a), (p_b, o_b, s_b, aux_b) = outs
    for la, lb in zip(jax.tree.leaves((p_a, o_a, s_a)),
                      jax.tree.leaves((p_b, o_b, s_b))):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    assert float(aux_a["grad_norm"]) == \
        pytest.approx(float(aux_b["grad_norm"]), rel=1e-6)


# ------------------------------------------ warm pipeline / telemetry
def test_warm_adds_zero_executables_with_fused():
    from paddle_tpu.profiler import compile_observatory as cobs
    from paddle_tpu.jit import warm as jwarm
    fused, _ = _pair(lambda m: opt.AdamW(learning_rate=1e-3,
                                         parameters=m.parameters()))
    x, y = _batch()
    jwarm.join([fused.warm(x, y)], record=False)
    warmed = cobs.ledger_signatures()
    float(fused(x, y).item())
    float(fused(x, y).item())
    assert cobs.ledger_signatures() == warmed, \
        "steady state compiled beyond the warmed set"
    assert fused.retraces == 1


def test_step_record_carries_epilogue_split(tmp_path, monkeypatch):
    mfile = tmp_path / "m.jsonl"
    monkeypatch.setenv("PADDLE_TPU_METRICS_FILE", str(mfile))
    fused, _ = _pair(lambda m: opt.AdamW(learning_rate=1e-3,
                                         parameters=m.parameters()))
    x, y = _batch()
    for _ in range(3):
        float(fused(x, y).item())
    recs = [json.loads(l) for l in open(mfile)]
    steps = [r for r in recs if r.get("kind") == "step"]
    assert steps and all("epilogue_bytes" in r for r in steps)
    assert all(r["epilogue_bytes"] == fused._epilogue_bytes
               for r in steps)
    assert all(0.0 <= r["epilogue_share"] <= 1.0 for r in steps)
    import importlib.util as ilu
    spec = ilu.spec_from_file_location(
        "cms", os.path.join(os.path.dirname(__file__), "..", "tools",
                            "check_metrics_schema.py"))
    cms = ilu.module_from_spec(spec)
    spec.loader.exec_module(cms)
    assert cms.validate_file(str(mfile)) == []


def test_sync_to_model_roundtrip():
    fused, tree = _pair(lambda m: opt.AdamW(learning_rate=1e-2,
                                            parameters=m.parameters()))
    x, y = _batch()
    float(fused(x, y).item()), float(tree(x, y).item())
    fused.sync_to_model()
    tree.sync_to_model()
    np.testing.assert_array_equal(
        np.asarray(fused.model[0].weight.value),
        np.asarray(tree.model[0].weight.value))


# --------------------------------------------------- hybrid (per-shard)
def _hybrid_pair(mesh, make_opt, scaler=None, **kw):
    from paddle_tpu.distributed.fleet.hybrid_train import HybridTrainStep
    steps = []
    for fused in (True, False):
        m = _model(7)
        o = make_opt(m)
        sc = GradScaler(**scaler) if scaler else None
        steps.append(HybridTrainStep(m, _loss_fn, o, mesh, scaler=sc,
                                     fused_update=fused, **kw))
    assert steps[0]._fused is not None and steps[1]._fused is None
    return steps


def _hybrid_batch():
    x = paddle.to_tensor(
        np.random.RandomState(1).randn(8, 8).astype(np.float32))
    y = paddle.to_tensor(np.arange(8, dtype=np.int64) % 4)
    return x, y


@pytest.mark.parametrize("stage", [1, 2, 3])
def test_hybrid_fused_equals_tree(stage):
    from paddle_tpu.distributed.env import build_mesh
    mesh = build_mesh(dp=2, mp=2, sharding=2)
    fused, tree = _hybrid_pair(
        mesh,
        lambda m: opt.AdamW(learning_rate=1e-3,
                            parameters=m.parameters(),
                            grad_clip=ClipGradByGlobalNorm(0.5)),
        scaler={"init_loss_scaling": 2.0 ** 8},
        sharding_stage=stage)
    x, y = _hybrid_batch()
    for _ in range(3):
        lf, lt = float(fused(x, y).item()), float(tree(x, y).item())
        assert lf == pytest.approx(lt, rel=1e-5)
    for k in fused.params:
        np.testing.assert_allclose(
            np.asarray(jax.device_get(fused.params[k]), np.float32),
            np.asarray(jax.device_get(tree.params[k]), np.float32),
            rtol=3e-6, atol=1e-7, err_msg=f"param {k} (stage {stage})")
    assert float(fused.scaler_state["scale"]) == \
        float(tree.scaler_state["scale"])


def test_hybrid_fused_health_and_psum_norm():
    """The ONE psum'd global norm must equal the tree-path norm even
    with leaves replicated over dp (norm_weight de-duplication)."""
    from paddle_tpu.distributed.env import build_mesh
    mesh = build_mesh(dp=4, mp=2)
    fused, tree = _hybrid_pair(
        mesh,
        lambda m: opt.AdamW(learning_rate=1e-3,
                            parameters=m.parameters()),
        monitor_health=True)
    x, y = _hybrid_batch()
    for _ in range(2):
        float(fused(x, y).item()), float(tree(x, y).item())
    hf, ht = fused.flush_health(), tree.flush_health()
    for k in ("loss", "grad_norm", "param_norm", "update_ratio"):
        assert hf[k] == pytest.approx(ht[k], rel=1e-5), k
