"""The trace reduction on a recorded chip trace
(testdata/small.xplane.pb: three runs of a two-matmul program on a
TPU v5 lite, 20 ms of host sleep between them, recorded by
tools/record_testdata.py; its window was 65.0 ms)."""
import os

import pytest

from benchmarks.lib import xplane

PB = os.path.join(os.path.dirname(os.path.dirname(__file__)), "testdata",
                  "small.xplane.pb")
WINDOW_S = 0.06502538800000224


@pytest.fixture(scope="module")
def trace():
    return xplane.Trace.from_file(PB, WINDOW_S)


def test_op_name_is_the_defined_op():
    assert xplane.op_name(
        "%ssm_scan.73 = (f32[512,4096]{1,0}) custom-call(%x)") == "ssm_scan.73"
    assert xplane.op_name(
        "%fusion.5 = f32[8] fusion(f32[8] %ssm_scan.73)") == "fusion.5"


def test_union_and_overlap():
    total, merged = xplane.union_ns([(0, 10), (5, 20), (30, 40)])
    assert total == 30 and merged == [[0, 20], [30, 40]]
    assert xplane.overlap_ns((15, 35), merged) == 10


def test_busy_idle_of_the_fullest_device(trace):
    assert trace.fullest == "/device:TPU:0"
    # 3 runs x (13 + 3 + ~14.8k + ~12.6k) ns of ops, by hand from the file
    assert trace.busy_fullest_s == pytest.approx(82.2e-6, rel=0.01)
    assert trace.busy_s == trace.busy_fullest_s        # one device
    assert trace.idle_share() == pytest.approx(1 - 82.2e-6 / WINDOW_S,
                                               rel=1e-4)


def test_kernel_time_by_name_pattern(trace):
    s, calls = trace.kernel_seconds(r"^convolution_tanh_fusion")
    assert calls == 3 and s == pytest.approx(44.32e-6, rel=1e-3)
    assert trace.kernel_seconds("ssm_scan") == (0.0, 0)
    assert trace.module_runs("jit_toy_step") == 3


def test_no_collective_on_one_chip(trace):
    assert trace.exposed_collective_seconds() == 0.0


def test_exposed_collective_arithmetic():
    ops = {"/device:TPU:0": [("fusion.1", 0, 100), ("all-reduce.1", 50, 150),
                             ("all-gather.2", 200, 260),
                             ("fusion.2", 220, 300)]}
    t = xplane.Trace(ops, {}, [], 1e-6)
    # all-reduce: 50 of 100 ns hidden; all-gather: 40 of 60 hidden
    assert t.exposed_collective_seconds() == pytest.approx(70e-9)


def test_breakdown(trace):
    b = trace.breakdown()
    assert [n for n, _ in b["device_ops"]][:2] == ["convolution_tanh_fusion",
                                                   "fusion"]
    # the two long gaps lie under the host's bench.wait spans
    (name, seconds), = b["idle_gaps"][:1]
    assert name == "bench.wait" and seconds == pytest.approx(42.4e-3,
                                                             rel=0.02)
