"""Worker for test_elastic_drill.py: deterministic training under
ElasticController in three modes —

  baseline N   : run N steps uninterrupted, dump all losses
  crash K      : run under the controller, hard-die (os._exit) after K
                 steps — simulating host preemption mid-training
  resume N     : ElasticController.maybe_resume() from the newest async
                 checkpoint, continue to step N, dump resumed losses

The model is dropout-free so the loss trajectory is a pure function of
(params, opt state, step) — exact-replay is the assertion.
"""
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402


def build():
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu import optimizer as opt
    from paddle_tpu.distributed import fleet

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs["dp_degree"] = 8
    fleet.init(is_collective=True, strategy=strategy)
    paddle.seed(0)
    m = nn.Sequential(nn.Linear(16, 32), nn.Tanh(), nn.Linear(32, 1))
    o = opt.AdamW(learning_rate=1e-2, parameters=m.parameters())

    def loss_fn(out, y):
        return paddle.mean(paddle.nn.functional.square_error_cost(out, y))

    step = fleet.build_train_step(m, loss_fn, o)
    rs = np.random.RandomState(0)
    X = rs.randn(32, 16).astype("float32")
    Y = (X @ rs.randn(16, 1)).astype("float32")
    return step, paddle.to_tensor(X), paddle.to_tensor(Y)


def main():
    mode, arg, ckpt_dir, out_path = (sys.argv[1], int(sys.argv[2]),
                                     sys.argv[3], sys.argv[4])
    from paddle_tpu.distributed.elastic import ElasticController

    step, X, Y = build()
    ctl = ElasticController(step, ckpt_dir, save_every_steps=2,
                            watchdog_timeout_s=3600)
    start = ctl.maybe_resume()
    losses = {}
    target = arg if mode != "crash" else 10 ** 9
    i = start
    while i < target:
        loss = float(step(X, Y))
        i = step._step_i
        ctl.on_step()
        losses[i] = loss
        if mode == "crash" and i >= arg:
            # let the async checkpoint writer drain, then die like a
            # preempted host — no cleanup, no stop()
            ctl.wait()
            os._exit(17)
    ctl.stop()
    with open(out_path, "w") as f:
        json.dump({"start": start, "losses": losses}, f)


if __name__ == "__main__":
    main()
