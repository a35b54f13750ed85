"""The traffic generator and the signature closure."""
import numpy as np

from benchmarks.lib import signatures, traffic

CHAT = {"loop": "open", "rate_rps": 20.0, "horizon_s": 10.0,
        "burst": {"every_s": 5.0, "length_s": 1.0, "factor": 2.0},
        "prompt": {"lo": 32, "hi": 1024, "median": 128, "sigma": 0.9},
        "output": {"lo": 32, "hi": 256, "median": 96, "sigma": 0.6},
        "max_total": 1280}


def test_same_seed_same_traffic():
    a = traffic.open_loop(CHAT, 1000, 3000000001)
    b = traffic.open_loop(CHAT, 1000, 3000000001)
    assert [r["t"] for r in a] == [r["t"] for r in b]
    assert all((x["prompt"] == y["prompt"]).all() for x, y in zip(a, b))


def test_every_seed_the_same_sizes_in_another_order():
    """A seed moves the interleaving, not the amount of work: the same
    multiset of lengths and gaps is generated, and what falls inside the
    horizon differs by the few requests the order puts past its end."""
    runs = [traffic.open_loop(CHAT, 1000, s) for s in (1, 2, 2 ** 31 + 5)]
    counts = [len(r) for r in runs]
    assert max(counts) - min(counts) <= 0.1 * max(counts)
    work = [sum(len(r["prompt"]) + r["max_new"] for r in run)
            for run in runs]
    assert max(work) - min(work) <= 0.15 * max(work)
    assert runs[0][0]["t"] != runs[1][0]["t"]
    for run in runs:
        ts = [r["t"] for r in run]
        assert ts == sorted(ts) and ts[-1] < CHAT["horizon_s"]
        p = np.array([len(r["prompt"]) for r in run])
        assert p.min() >= 32 and p.max() <= 1024
        assert 90 <= np.median(p) <= 180
        assert all(32 <= r["max_new"] <= 256 for r in run)


def test_bursts_arrive_faster():
    run = traffic.open_loop(dict(CHAT, horizon_s=50.0), 1000, 7)
    t = np.array([r["t"] for r in run])
    in_burst = (t % 5.0) < 1.0
    rate_in = in_burst.sum() / (50.0 * 0.2)
    rate_out = (~in_burst).sum() / (50.0 * 0.8)
    assert 1.5 < rate_in / rate_out < 2.6


def test_closed_loop_clients_cycle_the_same_sizes():
    tr = {"loop": "closed", "clients": 2, "requests_per_client": 4,
          "prompt": CHAT["prompt"], "output": CHAT["output"],
          "max_total": 1024}
    size = lambda r: (len(r["prompt"]), r["max_new"])
    a = traffic.closed_loop(tr, 1000, 9, min_requests=10)
    b = traffic.closed_loop(tr, 1000, 10, min_requests=10)
    assert len(a) == 2 and all(len(c) == 12 for c in a)   # 3 cycles of 4
    for c in a:
        assert [size(r) for r in c[:4]] == [size(r) for r in c[4:8]]
        assert not (c[0]["prompt"] == c[4]["prompt"]).all()   # fresh ids
    # another seed: the same sizes in the same order, other token ids
    assert [[size(r) for r in c] for c in a] == \
        [[size(r) for r in c] for c in b]
    assert not (a[0][0]["prompt"] == b[0][0]["prompt"]).all()
    assert all(len(r["prompt"]) + r["max_new"] <= 1024
               for c in a for r in c)


def test_recurrent_closure_covers_what_a_step_can_carry():
    sigs = set(signatures.recurrent_closure(8, 32))
    assert (8, 1, 1) in sigs               # one decode row
    assert (64, 8, 1) in sigs              # 8 rows, 7 decoding + 32 prompt
    assert (64, 1, 1) not in sigs          # one row carries 32 at most
    assert all(t >= 8 and b <= 8 and w == 1 for t, b, w in sigs)
    assert len(signatures.recurrent_closure(16, 64)) == 23
