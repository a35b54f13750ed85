"""Driver for test_spawn_multiprocess: paddle.distributed.spawn with
nprocs=2 on the pinned CPU backend — each rank must join a real
2-process jax.distributed world."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("XLA_FLAGS", None)  # exactly 1 local CPU device per proc


def train(tag):
    import jax
    jax.config.update("jax_platforms", "cpu")
    import paddle_tpu.distributed as dist
    print(f"{tag} rank={dist.get_rank()} world={jax.process_count()}",
          flush=True)
    assert jax.process_count() == 2


if __name__ == "__main__":
    import paddle_tpu.distributed as dist
    dist.spawn(train, args=("spawned",), nprocs=2)
