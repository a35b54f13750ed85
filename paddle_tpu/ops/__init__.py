"""paddle_tpu.ops — hand-written Pallas TPU kernels for the hot paths
(SURVEY.md §6): flash attention, fused layer_norm, softmax-cross-entropy.

Kernels run natively on TPU; on CPU (tests) they run in Pallas interpret
mode or as the XLA composition — chosen by the backend, never because a
kernel failed to import or lower.
"""
import contextlib
import os
import threading

import jax

_FLASH_ENV = os.environ.get("PADDLE_TPU_FLASH", "auto")


def _on_tpu():
    return jax.default_backend() == "tpu"


def flash_attention_available():
    """Whether scaled_dot_product_attention takes the Pallas kernel: on
    a TPU backend (or in interpret mode when PADDLE_TPU_FLASH says so),
    unless PADDLE_TPU_FLASH=0. A kernel module that cannot be imported
    is an ERROR here, not a quiet switch to the plain composition."""
    if _FLASH_ENV == "0":
        return False
    if not (_on_tpu() or _FLASH_ENV == "interpret"):
        return False
    from .pallas import flash_attention as _  # noqa: F401
    return True


_tracing = threading.local()


@contextlib.contextmanager
def kernels_partitioned_over(mesh, batch_axis, head_axis):
    """Entered by the layer that BUILDS an auto-partitioned SPMD program
    (HybridTrainStep, around the model's forward while it traces): a
    Mosaic kernel cannot be partitioned automatically, so inside the
    scope flash_attention runs per shard of `mesh`, batch over
    `batch_axis` and heads over `head_axis`. Outside it — a one-chip
    step, or the body of a shard_map (LocalSGD, PipelineParallel), where
    arrays already are the per-device block — the kernel is called bare.
    The scope is the tracing thread's and ends with the `with`."""
    prev = getattr(_tracing, "partition", None)
    _tracing.partition = (mesh, batch_axis, head_axis)
    try:
        yield
    finally:
        _tracing.partition = prev


def flash_attention(q, k, v, causal=False, scale=None, window=None):
    from .pallas.flash_attention import flash_attention as fa
    return fa(q, k, v, causal=causal, scale=scale, window=window,
              partition=getattr(_tracing, "partition", None))


def fused_layer_norm_available():
    return _on_tpu()


def fused_layer_norm(x, weight, bias, eps=1e-5):
    from .pallas.layer_norm import layer_norm as ln
    return ln(x, weight, bias, eps)


from .block_sparse import (block_sparse_attention,  # noqa: E402
                           block_sparse_attention_arrays,
                           local_strided_pattern)

from .paged_attention import PagedKVCache, paged_attention  # noqa: E402


def ragged_paged_attention(*args, **kwargs):
    """Mixed prefill+decode paged attention (lazy import: the Pallas
    module stays off the package-import path, like flash_attention)."""
    from .pallas.paged_attention import ragged_paged_attention as rpa
    return rpa(*args, **kwargs)


def ragged_work_plan(bounds, page_size):
    from .pallas.paged_attention import ragged_work_plan as rwp
    return rwp(bounds, page_size)
