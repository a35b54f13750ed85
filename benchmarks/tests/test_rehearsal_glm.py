"""The runner's control flow for `glm-4.7-flash-ep8.train.b2-s4096` on
the CPU, on a tiny copy of the configuration (tests/tiny.py keys its
tiny sizes by configuration and holds none for this one, so the copy and
the patches are made here): the last line's keys, a sound run judged
correct with the cell's own per-layer metrics read from the program's
counters, and the half-batch fault judged not correct. By hand, as this
directory is: `python -m pytest benchmarks/tests/test_rehearsal_glm.py`."""
import copy
import json
import os

import numpy as np
import pytest

from benchmarks import run
from benchmarks.lib import peaks
from benchmarks.lib import program as P
from benchmarks.lib import xplane

CELL = "glm-4.7-flash-ep8.train.b2-s4096"
SEED = 3000000027
TINY = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=3, num_attention_heads=4, q_lora_rank=32,
            kv_lora_rank=16, qk_nope_head_dim=24, qk_rope_head_dim=8,
            v_head_dim=32, moe_intermediate_size=48, n_routed_experts=2,
            router_experts=8, local_expert_start=2, num_experts_per_tok=2)
# the tiny copy in bf16 against the float32 reference (tests/
# test_decoder_moe.py gives the readings these stand on)
LIMITS = {"loss1_gap": 1e-4, "loss2_gap": 1e-4, "grad_norm_gap": 0.08,
          "change_norm_gap": 0.05}
RECORDED = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "testdata", "small.xplane.pb")


@pytest.fixture
def cpu_runner(monkeypatch):
    import jax
    real = P.load_cell

    def load_cell(name):
        cell, config, entry, manifest = real(name)
        cell, config = copy.deepcopy(cell), copy.deepcopy(config)
        config.update(TINY)
        config["program"]["kwargs"] = dict(TINY)
        cell.update(batch=2, seq=32, distinct_batches=4,
                    reference_micro_batch=2)
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
    # the recorded trace holds none of this cell's kernels: their
    # rooflines are left out, never a 0
    assert set(got) == {"step_mfu_moe.train",
                        "expert_load_max_over_mean.train",
                        "moe_dropped_assignments.train"}
    assert got["moe_dropped_assignments.train"]["value"] == 0
    assert 1.0 <= got["expert_load_max_over_mean.train"]["value"] <= 2.0
    assert got["step_mfu_moe.train"]["value"] > 0
    assert all(np.isfinite(m["value"]) for m in got.values())
    assert line["correct"] is True, line["compared"]


def test_half_batch_is_not_correct(cpu_runner, monkeypatch):
    from benchmarks.lib import train
    whole = train.loss_fn

    def half(logits, labels):
        n = logits.shape[0] // 2
        return whole(logits[:n], labels[:n])

    monkeypatch.setattr(train, "loss_fn", half)
    line = run.run_cell(CELL, SEED + 2, 1.0, 0)
    assert line["correct"] is False, line["compared"]


def test_readers_find_nothing_without_the_programs_counters(monkeypatch):
    """On a commit whose program counts no assignments (the parent) the
    new readers return None and do not raise."""
    from paddle_tpu.profiler import monitor
    from benchmarks.readers import (mla_flash_roofline, moe_counters,
                                    step_mfu_moe)
    monitor.reset_metrics()
    cfg = P.load_json("configs", "glm-4.7-flash-ep8.json")
    trace = xplane.Trace.from_file(RECORDED, 1.0)
    ctx = {"config": cfg, "trace": trace, "chips": 1,
           "peak": peaks.PEAKS["TPU v5 lite"],
           "window": {"kind": "train", "tokens": 8192, "seq": 4096,
                      "batch": 2, "window_s": 1.0}}
    assert step_mfu_moe.read(ctx) is None
    for what in ("dropped", "load_max_over_mean", "local_share"):
        assert moe_counters.read(ctx, what) is None
    assert mla_flash_roofline.read(ctx, ["flash_attention_dq"]) is None


def test_required_work_of_the_cell():
    from benchmarks.lib import work_moe as W
    cfg = P.load_json("configs", "glm-4.7-flash-ep8.json")
    assert W.mla_matmul_params(cfg) == pytest.approx(21.76e6, rel=1e-3)
    assert W.expert_params(cfg) == 3 * 2048 * 1536
    t = 8192
    flops = W.train_flops(cfg, 4096, t, W.expected_local_assignments(cfg, t))
    assert flops / t == pytest.approx(2.24e9, rel=2e-3)     # ISSUE 27
    flash = W.mla_flash_work(cfg, 2, 4096)
    assert flash["flash_attention_fwd"]["flops"] == pytest.approx(
        2 * 2.0 * 2 * 20 * (4096 * 4097 / 2) * 256)
