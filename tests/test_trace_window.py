"""The traced window's definition, held by tier-1 (the same synthetic
cases as benchmarks/tests/test_xplane.py, which tier-1 does not
collect): benchmarks.lib.xplane.Trace divides every reading by the
longer of the host's window and the recorded extent of the device's ops,
so 0 < busy_s <= window_s and 0 <= idle_share() < 1 hold for every trace
with a device op, and a trace without one is refused."""
import os

import pytest

from benchmarks.lib import xplane

PB = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmarks", "testdata", "small.xplane.pb")
WINDOW_S = 0.06502538800000224


@pytest.fixture(scope="module")
def trace():
    return xplane.Trace.from_file(PB, WINDOW_S)


def test_union_and_overlap():
    total, merged = xplane.union_ns([(0, 10), (5, 20), (30, 40)])
    assert total == 30 and merged == [[0, 20], [30, 40]]
    assert xplane.overlap_ns((15, 35), merged) == 10


# ---- the traced window: the longer of the host's and the device's ------
S = 10 ** 9                     # the tuples are in ns
HOST_S = 4.0
PAST_THE_EDGE = {
    # the recorded extent outlasts the host's window by 130 us and
    # holds 240 us of gaps, so the busy time still fits the host's
    # window, by 110 us
    "by_less_than_the_gaps": (
        {"/device:TPU:0": [("fusion.1", 0, S), ("fusion.2", S + 100_000, 2 * S),
                           ("fusion.3", 2 * S + 140_000, 4 * S + 130_000)]},
        4 * S + 130_000, 240_000),
    # it outlasts the host's window by more than its gaps: busy_s over
    # the host's window alone would read above 1
    "by_more_than_the_gaps": (
        {"/device:TPU:0": [("fusion.1", 0, 2 * S),
                           ("fusion.2", 2 * S + 100_000, 4 * S + 500_000)]},
        4 * S + 500_000, 100_000),
    # never idle at all: the whole extent is busy
    "no_gap": ({"/device:TPU:0": [("fusion.1", 7, 4 * S + 1_000_007)]},
               4 * S + 1_000_000, 0),
}


@pytest.mark.parametrize("case", sorted(PAST_THE_EDGE))
def test_a_device_busier_than_the_hosts_window_sets_the_window(case):
    ops, span_ns, gaps_ns = PAST_THE_EDGE[case]
    t = xplane.Trace(ops, {}, [], HOST_S)
    assert t.host_window_s == HOST_S and t.span_s == span_ns / 1e9
    assert t.window_s == t.span_s > t.host_window_s
    assert 0 < t.busy_s <= t.window_s
    assert t.busy_s == pytest.approx((span_ns - gaps_ns) / 1e9, abs=1e-12)
    # the idle share is the gaps over the extent, which is what the
    # device did; never under 0
    assert 0 <= t.idle_share() < 1
    assert t.idle_share() == pytest.approx(gaps_ns / span_ns, abs=1e-12)


def test_the_window_spans_every_plane():
    # chip 1 starts a second late and ends 200 ms past the host's window
    ops = {"/device:TPU:0": [("fusion.1", 0, 3 * S)],
           "/device:TPU:1": [("fusion.1", S, 2 * S),
                             ("fusion.2", 2 * S + 50_000_000,
                              4 * S + 200_000_000)]}
    t = xplane.Trace(ops, {}, [], HOST_S)
    assert t.span_s == 4.2 and t.window_s == 4.2
    assert t.fullest == "/device:TPU:1" and t.busy_fullest_s == 3.15
    assert t.busy_s == pytest.approx((3.0 + 3.15) / 2)
    assert 0 < t.busy_s <= t.busy_fullest_s <= t.window_s
    assert t.idle_share() == pytest.approx(1 - 3.15 / 4.2)


def test_the_hosts_window_stands_where_it_is_the_longer(trace):
    # nearly every run: nothing reads otherwise than before
    ops = {"/device:TPU:0": [("fusion.1", 0, 2 * S),
                             ("fusion.2", 2 * S + 400_000, 4 * S - 300_000)]}
    t = xplane.Trace(ops, {}, [], HOST_S)
    assert t.window_s == t.host_window_s == HOST_S
    assert t.span_s == (4 * S - 300_000) / 1e9
    assert t.busy_s == (4 * S - 700_000) / 1e9
    assert t.idle_share() == 1.0 - t.busy_s / HOST_S
    # and the recorded trace: its ops' extent is 43 ms of the 65 ms
    assert trace.window_s == trace.host_window_s == WINDOW_S
    assert trace.span_s == pytest.approx(43.01e-3, rel=1e-3)


def test_any_trace_with_a_device_op_reads_inside_its_window():
    import random
    rng = random.Random(33)
    for _ in range(300):
        ops = {}
        for d in range(rng.choice((1, 1, 4))):
            t0, ev = rng.randrange(10 ** 6), []
            for i in range(rng.randrange(1, 6)):
                t0 += rng.randrange(0, 2000)
                ev.append((f"fusion.{i}", t0, t0 + rng.randrange(1, 10 ** 6)))
                t0 = ev[-1][2] - rng.randrange(0, 2)     # some overlap
            ops[f"/device:TPU:{d}"] = ev
        for host_s in (1e-9, 1e-3, 10.0):
            t = xplane.Trace(ops, {}, [], host_s)
            assert 0 < t.busy_s <= t.busy_fullest_s <= t.window_s
            assert 0 <= t.idle_share() < 1
            assert t.window_s == max(host_s, t.span_s)


def test_a_trace_without_device_time_is_refused():
    for ops in ({}, {"/device:TPU:0": []},
                {"/device:TPU:0": [("copy-start.1", 5, 5)]}):
        with pytest.raises(RuntimeError, match="no device operation"):
            xplane.Trace(ops, {}, [], 1.0)
