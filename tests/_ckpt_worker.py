"""Worker for test_fault_tolerance.py kill-and-resume drills:
deterministic training under ElasticController/CheckpointManager in two
step flavors —

    python _ckpt_worker.py <single|hybrid> <target_step> <ckpt_dir> <out.json>

Train (resuming from the newest verified checkpoint when one exists) to
`target_step`, checkpointing every CKPT_SAVE_EVERY (default 2) steps,
then dump {"start", "losses", "digest", "step"} to out.json. The digest
is a sha256 over EVERY state leaf's raw bytes (params + optimizer state
+ scaler state + step counter), so "bit-identical resume" is literal.

Faults are injected by the PARENT via PADDLE_TPU_FAULT_SPEC (e.g.
`kill@ckpt.write#15` → SIGKILL while the background writer streams the
second checkpoint's shards): this worker needs no fault-specific code —
which is the point of the harness (framework/fault_injection.py).

The model is dropout-free so the loss trajectory is a pure function of
(params, opt state, scaler state, step) — exact replay is the
assertion. The single-step flavor carries a GradScaler so scaler state
rides the checkpoint too.
"""
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["PADDLE_TPU_COMPILE_CACHE"] = "0"
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402


def build(flavor):
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu import optimizer as opt

    paddle.seed(0)
    m = nn.Sequential(nn.Linear(16, 32), nn.Tanh(), nn.Linear(32, 1))
    o = opt.AdamW(learning_rate=1e-2, parameters=m.parameters())

    def loss_fn(out, y):
        return paddle.mean(paddle.nn.functional.square_error_cost(out, y))

    if flavor == "hybrid":
        from paddle_tpu.distributed import fleet
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs["dp_degree"] = 4
        strategy.hybrid_configs["mp_degree"] = 2
        fleet.init(is_collective=True, strategy=strategy)
        step = fleet.build_train_step(m, loss_fn, o)
    else:
        from paddle_tpu.jit import TrainStep
        scaler = paddle.amp.GradScaler(init_loss_scaling=2.0 ** 10,
                                       incr_every_n_steps=3)
        step = TrainStep(m, loss_fn, o, scaler=scaler)
    rs = np.random.RandomState(0)
    X = rs.randn(32, 16).astype("float32")
    Y = (X @ rs.randn(16, 1)).astype("float32")
    return step, paddle.to_tensor(X), paddle.to_tensor(Y)


def digest(step):
    """sha256 over every state leaf's raw bytes + the step counter."""
    import hashlib
    from jax.tree_util import tree_flatten_with_path, keystr
    h = hashlib.sha256()
    for p, leaf in tree_flatten_with_path(step.tree_state())[0]:
        h.update(keystr(p).encode())
        h.update(np.asarray(jax.device_get(leaf)).tobytes())
    h.update(str(int(step._step_i)).encode())
    return h.hexdigest()


def main():
    flavor, target, ckpt_dir, out_path = (
        sys.argv[1], int(sys.argv[2]), sys.argv[3], sys.argv[4])
    save_every = int(os.environ.get("CKPT_SAVE_EVERY", "2"))
    from paddle_tpu.distributed.elastic import ElasticController

    step, X, Y = build(flavor)
    ctl = ElasticController(step, ckpt_dir, save_every_steps=save_every,
                            watchdog_timeout_s=3600)
    start = ctl.maybe_resume()
    losses = {}
    i = start
    while i < target:
        loss = float(step(X, Y))
        i = int(step._step_i)
        losses[i] = loss
        ctl.on_step()
    # drain the background writer: an injected kill mid-write fires
    # HERE at the latest (the process dies before reporting — exactly
    # the preemption the resume run must recover from)
    ctl.wait()
    ctl.stop()
    with open(out_path, "w") as f:
        json.dump({"start": start, "losses": losses,
                   "digest": digest(step), "step": int(step._step_i)}, f)


if __name__ == "__main__":
    main()
