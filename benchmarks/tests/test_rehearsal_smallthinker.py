"""The runner's control flow for `smallthinker-21b-ep8.train.b1-s16384`
on the CPU, on a tiny copy of the configuration (tests/tiny.py keys its
tiny sizes by configuration and holds none for this one, so the copy and
the patches are made here, as test_rehearsal_glm.py does): the last
line's keys, a sound run judged correct with the cell's five per-layer
metrics' readers asked, the half-batch fault judged not correct (on a
copy of two rows: the fault halves rows, and the cell has one), the new
readers on a program that lacks what they read, and the cell's required
work. By hand, as this directory is:
`python -m pytest benchmarks/tests/test_rehearsal_smallthinker.py`."""
import copy
import json
import os

import numpy as np
import pytest

from benchmarks import run
from benchmarks.lib import peaks
from benchmarks.lib import program as P
from benchmarks.lib import xplane

CELL = "smallthinker-21b-ep8.train.b1-s16384"
SEED = 3000000031
# one period: a full layer without positions, three window layers with
# rotary; 2 of 8 experts held; a window the 32 tokens overflow
TINY = dict(vocab_size=256, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, sliding_window_size=8,
            n_routed_experts=2, router_experts=8, local_expert_start=2,
            num_experts_per_tok=2, moe_intermediate_size=48)
PUBLISHED_NAMES = dict(moe_num_primary_experts=2,
                       moe_num_active_primary_experts=2,
                       moe_ffn_hidden_size=48)
# the tiny copy in bf16 against the float32 reference
# (tests/test_decoder_swa_moe.py gives the readings these stand on: sound
# runs' loss gaps up to 3.7e-5, grad_norm_gap up to 0.017)
LIMITS = {"loss1_gap": 1e-4, "loss2_gap": 1e-4, "grad_norm_gap": 0.08,
          "change_norm_gap": 0.05}
RECORDED = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "testdata", "small.xplane.pb")


def patch_runner(monkeypatch, batch):
    import jax
    real = P.load_cell

    def load_cell(name):
        cell, config, entry, manifest = real(name)
        cell, config = copy.deepcopy(cell), copy.deepcopy(config)
        config.update(TINY, **PUBLISHED_NAMES)
        config["program"]["kwargs"] = dict(TINY)
        cell.update(batch=batch, seq=32, distinct_batches=4,
                    reference_micro_batch=batch)
        cell["correct"]["limits"] = dict(LIMITS)
        return cell, config, entry, manifest

    monkeypatch.setattr(P, "load_cell", load_cell)
    monkeypatch.setattr(P, "require_tpu", lambda chips: jax.devices()[:chips])
    monkeypatch.setitem(peaks.PEAKS, jax.devices()[0].device_kind,
                        peaks.PEAKS["TPU v5 lite"])
    from benchmarks.lib.tracing import Tracer
    monkeypatch.setattr(
        Tracer, "reduce",
        lambda self: xplane.Trace.from_file(RECORDED, self.window_s))


@pytest.fixture
def cpu_runner(monkeypatch):
    patch_runner(monkeypatch, batch=1)


def test_end_to_end_line(cpu_runner):
    line = run.run_cell(CELL, SEED, 1.5, 0)
    assert list(line)[-1] == "compared"
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    json.dumps(line)


def test_traced_line_reads_the_programs_counters(cpu_runner):
    line = run.run_cell(CELL, SEED + 1, 1.5, 1)
    got = line["metrics"]
    # off the chip attention takes the plain composition and the
    # recorded trace holds none of this cell's kernels: the roofline and
    # the visited share are left out, never a 0
    assert set(got) == {"step_mfu_swa_moe.train",
                        "pre_router_load_max_over_mean.train",
                        "pre_router_dropped_assignments.train"}
    assert got["pre_router_dropped_assignments.train"]["value"] == 0
    assert 1.0 <= got["pre_router_load_max_over_mean.train"]["value"] <= 2.0
    assert got["step_mfu_swa_moe.train"]["value"] > 0
    assert all(np.isfinite(m["value"]) for m in got.values())
    assert line["correct"] is True, line["compared"]


def test_visited_share_is_read_where_the_kernels_trace(monkeypatch):
    """The fifth metric: a traced windowed call observes its share, and
    the reader returns the mean in %."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import attention_core as core
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_arrays
    from paddle_tpu.profiler import monitor
    from benchmarks.readers import flash_window_visited_share as reader
    monitor.reset_metrics()
    assert reader.read({}) is None
    q = jax.ShapeDtypeStruct((1, 16384, 28, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 16384, 4, 128), jnp.bfloat16)
    jax.eval_shape(lambda q, k, v: flash_attention_arrays(
        q, k, v, causal=True, window=4096, interpret=True), q, kv, kv)
    got = reader.read({})
    assert got == pytest.approx(
        100 * core.window_visited_share(16384, 128, 4096))
    assert 40 < got < 60        # the pairs alone give 43.75
    assert monitor.counter("flash.calls.gqa").value == 1
    assert monitor.counter("flash.calls.window").value == 1


def test_half_batch_is_not_correct(monkeypatch):
    patch_runner(monkeypatch, batch=2)
    from benchmarks.lib import train
    whole = train.loss_fn

    def half(logits, labels):
        n = logits.shape[0] // 2
        return whole(logits[:n], labels[:n])

    monkeypatch.setattr(train, "loss_fn", half)
    line = run.run_cell(CELL, SEED + 2, 1.0, 0)
    assert line["correct"] is False, line["compared"]


def test_readers_find_nothing_without_the_programs_counters(monkeypatch):
    """On a commit whose program counts no assignments and observes no
    window (the parent) the new readers return None and do not raise."""
    from paddle_tpu.profiler import monitor
    from benchmarks.readers import (flash_window_visited_share,
                                    gqa_window_flash_roofline, moe_counters,
                                    step_mfu_swa_moe)
    monitor.reset_metrics()
    cfg = P.load_json("configs", "smallthinker-21b-ep8.json")
    trace = xplane.Trace.from_file(RECORDED, 1.0)
    ctx = {"config": cfg, "trace": trace, "chips": 1,
           "peak": peaks.PEAKS["TPU v5 lite"],
           "window": {"kind": "train", "tokens": 16384, "seq": 16384,
                      "batch": 1, "window_s": 1.0}}
    assert step_mfu_swa_moe.read(ctx) is None
    assert flash_window_visited_share.read(ctx) is None
    for what in ("dropped", "load_max_over_mean"):
        assert moe_counters.read(ctx, what) is None
    assert gqa_window_flash_roofline.read(
        ctx, ["flash_attention_dq"]) is None


def test_required_work_of_the_cell():
    from benchmarks.lib import work_swa_moe as W
    cfg = P.load_json("configs", "smallthinker-21b-ep8.json")
    assert W.windows(cfg) == [None, 4096, 4096, 4096]
    assert W.attention_matmul_params(cfg) == pytest.approx(20.97e6, rel=1e-3)
    assert W.expert_params(cfg) == 3 * 2560 * 768
    assert W.fixed_matmul_params(cfg) == pytest.approx(133.2e6, rel=1e-3)
    t = 16384
    # a window layer computes 44% of the causal triangle
    assert W.visible_pairs(t, 4096) / W.visible_pairs(t) == pytest.approx(
        58.7e6 / 134.2e6, rel=2e-3)
    assert W.visible_pairs(8, 3) == 1 + 2 + 6 * 3
    assert W.visible_pairs(8, 8) == W.visible_pairs(8) == 36
    flops = W.train_flops(cfg, t, t, W.expected_local_assignments(cfg, t))
    assert flops / t == pytest.approx(1.72e9, rel=3e-3)     # ISSUE 31
    flash = W.flash_work(cfg, 1, t)
    pairs = W.visible_pairs(t) + 3 * W.visible_pairs(t, 4096)
    assert flash["flash_attention_fwd"]["flops"] == pytest.approx(
        2 * 2.0 * 28 * pairs * 128)
    # k, v at 4 heads: the forward reads q and writes out at 28, reads
    # k and v at 4
    assert flash["flash_attention_fwd"]["bytes"] == \
        4 * (2 * 28 + 2 * 4) * t * 128 * 2
