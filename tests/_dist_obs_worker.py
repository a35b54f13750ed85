"""Worker for tests/test_dist_observatory.py — run via
`python -m paddle_tpu.distributed.launch --nproc_per_node 4
 --log_dir LOGDIR tests/_dist_obs_worker.py OUTDIR STRAGGLER_RANK`.

Each of the 4 ranks joins the jax.distributed world through
init_parallel_env (which runs the distributed observatory's clock-sync
handshake), then trains a tiny LOCAL model (the CPU backend cannot run
cross-process computations — rank identity, the KV store, and the
shared rankstat directory are the cross-process surface under test).
Rank STRAGGLER_RANK carries a PR-11 fault injection
(`delay@train.step=0.3`), so its step times trail the group and rank
0's rankstat gather must emit a `kind:"event"` `event:"straggler"`
naming it. Every rank exports a Chrome trace stamped with its measured
clock offset and writes a summary JSON for the parent to assert on.
"""
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("XLA_FLAGS", None)  # exactly 1 local CPU device per proc

RANK = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
OUTDIR = sys.argv[1]
STRAGGLER = int(sys.argv[2])

# per-rank metrics JSONL (a shared append file across 4 processes would
# interleave) + tight cadences so a short run exercises everything
os.environ["PADDLE_TPU_METRICS_FILE"] = os.path.join(
    OUTDIR, f"metrics.rank{RANK}.jsonl")
os.environ.setdefault("PADDLE_TPU_RANKSTAT_EVERY", "2")
os.environ.setdefault("PADDLE_TPU_COLLECTIVE_SAMPLE", "1")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402


def main():
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.distributed as dist
    from paddle_tpu import optimizer as opt
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.framework import fault_injection
    from paddle_tpu.profiler import dist_observatory as dobs
    from paddle_tpu.profiler import trace_export

    dist.init_parallel_env()  # world bootstrap + clock-sync handshake
    assert jax.process_count() == 4, jax.process_count()

    if RANK == STRAGGLER:
        # the PR-11 fault harness: every train.step dispatch on THIS
        # rank sleeps 300 ms — the injected skew the gather must name
        fault_injection.configure("delay@train.step=0.3")

    paddle.seed(0)
    model = nn.Linear(8, 8)
    o = opt.SGD(learning_rate=0.01, parameters=model.parameters())
    step = TrainStep(model, lambda out, y: ((out - y) ** 2).mean(), o)
    x = paddle.to_tensor(np.random.RandomState(0).randn(4, 8)
                         .astype(np.float32))
    loss = None
    for _ in range(8):
        loss = step(x, x)
    float(loss.item())

    # an eager collective so kind:"collective" records exist per rank
    t = paddle.to_tensor(np.ones(256, np.float32))
    dist.all_reduce(t)
    dist.wait(t)

    # all ranks' step loops (and rankstat snapshots) done BEFORE rank
    # 0's final gather — the straggler's slow loop must have published
    from jax._src import distributed as _jdist
    _jdist.global_state.client.wait_at_barrier(
        "dist_obs_test_steps_done", 120000)
    final = dobs.emit_rankstat(force=True)
    assert final is not None

    trace_path = os.path.join(OUTDIR, f"trace.rank{RANK}.json")
    trace_export.write_chrome_trace(trace_path)

    with open(os.path.join(OUTDIR, f"rank{RANK}.json"), "w") as f:
        json.dump({
            "rank": RANK,
            "world": jax.process_count(),
            "clock_offset_s": dobs.clock_offset_s(),
            "rankstat": final,
            "collective_rollup": dobs.collective_rollup(),
            "trace": trace_path,
        }, f)


if __name__ == "__main__":
    main()
