"""Worker script for test_launch_multiproc.py — run via
`python -m paddle_tpu.distributed.launch --nnodes 2 --node_rank R
 --master 127.0.0.1:PORT tests/_launch_worker.py OUTDIR`.

Each process pins the CPU backend (1 local device), joins the 2-process
jax.distributed world through paddle_tpu.distributed.init_parallel_env,
runs a cross-process psum and a small data-parallel train step, and
writes its observations to OUTDIR/rank<r>.json for the parent to check.
"""
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("XLA_FLAGS", None)  # exactly 1 local CPU device per proc

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa


def main():
    outdir = sys.argv[1]
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist

    dist.init_parallel_env()
    rank = dist.get_rank()
    world = jax.process_count()
    assert world == 2, f"expected 2 processes, got {world}"
    assert jax.device_count() == 2, jax.device_count()

    mesh = Mesh(np.array(jax.devices()), ("dp",))

    # 1. cross-process collective: psum of the rank id
    from jax import shard_map

    @jax.jit
    def allsum(x):
        return shard_map(
            lambda v: jax.lax.psum(v, "dp"), mesh=mesh,
            in_specs=P("dp"), out_specs=P())(x)

    local = np.array([float(rank)], dtype=np.float32)
    global_x = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("dp")), local, (2,))
    summed = float(np.asarray(jax.device_get(allsum(global_x))))

    # 2. DP train step: replicated params, per-process batch shard, psum'd
    # grads -> params must end identical on both ranks
    rs = np.random.RandomState(0)  # SAME init on both ranks
    w0 = rs.randn(8, 1).astype(np.float32)
    Xall = rs.randn(16, 8).astype(np.float32)
    Yall = Xall @ np.full((8, 1), 0.5, np.float32)
    # each process holds its half of the global batch
    Xloc = Xall[rank * 8:(rank + 1) * 8]
    Yloc = Yall[rank * 8:(rank + 1) * 8]
    Xg = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("dp")), Xloc, (16, 8))
    Yg = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("dp")), Yloc, (16, 1))
    rep = NamedSharding(mesh, P())
    w = jax.device_put(jnp.asarray(w0), rep)

    @jax.jit
    def step(w, X, Y):
        def loss_fn(w_):
            return jnp.mean((X @ w_ - Y) ** 2)
        l, g = jax.value_and_grad(loss_fn)(w)
        return l, w - 0.1 * g   # XLA inserts the dp grad psum

    losses = []
    for _ in range(5):
        l, w = step(w, Xg, Yg)
        losses.append(float(np.asarray(jax.device_get(l))))

    with open(os.path.join(outdir, f"rank{rank}.json"), "w") as f:
        json.dump({"rank": rank, "world": world, "psum": summed,
                   "losses": losses,
                   "w": np.asarray(jax.device_get(w)).tolist()}, f)


if __name__ == "__main__":
    main()
