"""Collective communication API.

Parity: python/paddle/distributed/collective.py. Two modes:

- **SPMD (inside shard_map/jit over the mesh)**: wrappers over
  lax.psum / all_gather / ppermute / all_to_all keyed by mesh axis name.
  This is the TPU path — XLA emits ICI collectives.
- **Eager single-controller**: collectives act on a Tensor sharded over a
  mesh axis (all ranks' data is one array); e.g. all_reduce sums shards.
  This keeps dygraph test code from the reference runnable verbatim.
"""
import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..framework.core import Tensor, apply_op
from ..profiler import statistic as _stat
from ..profiler import monitor as _monitor
from ..profiler import dist_observatory as _dobs
from .env import get_mesh


def _payload_bytes(args):
    """Sum the byte size of every Tensor/array (or list of them) in
    `args`. Works on tracers too — shape/dtype are known under trace."""
    nbytes = 0
    stack = list(args)
    while stack:
        t = stack.pop()
        if isinstance(t, (list, tuple)):
            stack.extend(t)
            continue
        a = t.value if isinstance(t, Tensor) else t
        shape = getattr(a, "shape", None)
        dtype = getattr(a, "dtype", None)
        if shape is None or dtype is None:
            continue
        try:
            nbytes += int(np.prod(shape)) * np.dtype(dtype).itemsize
        except (TypeError, ValueError):
            continue
    return nbytes


def _any_traced(args):
    """Whether any Tensor/array (or list of them) in `args` is a jax
    tracer — i.e. this collective call is a trace-time INSERTION, not
    an eager execution (its host wall time is trace overhead, not
    communication)."""
    stack = list(args)
    while stack:
        t = stack.pop()
        if isinstance(t, (list, tuple)):
            stack.extend(t)
            continue
        a = t.value if isinstance(t, Tensor) else t
        if isinstance(a, jax.core.Tracer):
            return True
    return False


def _group_label(args, kwargs):
    """The process-group label of one collective call: an explicit
    Group's axis wins, else the first string/tuple positional (the SPMD
    functional collectives pass the mesh axis name there), else the
    default 'dp' axis."""
    g = kwargs.get("group")
    for cand in ([g] if g is not None else []) + list(args):
        if isinstance(cand, Group):
            return str(cand.axis)
        if isinstance(cand, str):
            return cand
        if isinstance(cand, tuple) and cand and all(
                isinstance(c, str) for c in cand):
            return "+".join(cand)
    return "dp"


def _instrumented(fn=None, *, payload=None):
    """Telemetry wrapper for a collective: per-kind call + payload-bytes
    counters, a host span, and the distributed observatory's rollup +
    sampled `kind:"collective"` record (op, group, bytes, wall_s,
    bus-bandwidth GB/s — profiler/dist_observatory.py). Called under
    trace (inside jit/shard_map) this tallies collectives INSERTED per
    traced program — once per compile, not per execution (the record is
    flagged `traced`); eager calls count one-for-one with real wall
    time.

    `payload` selects which positional args carry the transferred data
    (args -> sequence) for APIs that also take an output placeholder
    (reduce_scatter's dst tensor, alltoall's out list) — counting those
    would overstate the traffic by the output size."""
    if fn is None:
        return lambda f: _instrumented(f, payload=payload)
    import functools
    kind = fn.__name__

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        # bytes BEFORE the call: all_gather/alltoall mutate their list
        # arguments, so counting afterwards would tally outputs too
        sel = payload(args) if payload else args
        nbytes = _payload_bytes(sel)
        traced = _any_traced(sel)
        _stat.begin_span(f"collective.{kind}")
        try:
            return fn(*args, **kwargs)
        finally:
            dt = _stat.end_span()
            _monitor.counter(f"collective.{kind}.calls").inc()
            _monitor.counter(f"collective.{kind}.bytes").inc(nbytes)
            _dobs.record_collective(kind, _group_label(args, kwargs),
                                    nbytes, dt, traced=traced)
    return wrapper

__all__ = ["ReduceOp", "all_reduce", "all_gather", "broadcast", "reduce",
           "scatter", "alltoall", "send", "recv", "reduce_scatter",
           "split", "new_group", "wait", "get_group",
           "psum", "pmean", "pmax", "all_gather_axis", "ppermute",
           "all_to_all_axis", "axis_index"]


class ReduceOp:
    SUM = 0
    MAX = 1
    MIN = 2
    PROD = 3
    AVG = 4


class Group:
    def __init__(self, ranks, axis="dp", gid=0):
        self.ranks = ranks
        self.axis = axis
        self.id = gid
        self.nranks = len(ranks) if ranks else 1

    def get_group_rank(self, rank):
        return self.ranks.index(rank) if rank in self.ranks else -1


_groups = {0: Group(None, "dp", 0)}


def new_group(ranks=None, backend=None, axis="dp"):
    gid = max(_groups) + 1
    g = Group(ranks, axis, gid)
    _groups[gid] = g
    return g


def get_group(gid=0):
    return _groups.get(gid)


def _in_trace(x):
    return isinstance(x, jax.core.Tracer)


# ---- SPMD functional collectives (use inside shard_map) ----------------
@_instrumented
def psum(x, axis):
    return lax.psum(x, axis)


@_instrumented
def pmean(x, axis):
    return lax.pmean(x, axis)


@_instrumented
def pmax(x, axis):
    return lax.pmax(x, axis)


@_instrumented
def all_gather_axis(x, axis, tiled=True, gather_dim=0):
    return lax.all_gather(x, axis, axis=gather_dim, tiled=tiled)


@_instrumented
def ppermute(x, axis, perm):
    return lax.ppermute(x, axis, perm)


@_instrumented
def all_to_all_axis(x, axis, split_axis, concat_axis):
    return lax.all_to_all(x, axis, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


def axis_index(axis):
    return lax.axis_index(axis)


# ---- Eager controller-level API ---------------------------------------
def _axis_of(group):
    if isinstance(group, Group):
        return group.axis
    return "dp"


@_instrumented
def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    """Inside shard_map: psum over the group axis. Eager: identity on the
    single controller (the mesh owns all shards already)."""
    if _in_trace(tensor.value if isinstance(tensor, Tensor) else tensor):
        ax = _axis_of(group)
        fn = {ReduceOp.SUM: lax.psum, ReduceOp.MAX: lax.pmax,
              ReduceOp.MIN: lax.pmin,
              ReduceOp.AVG: lax.pmean}[op]
        if isinstance(tensor, Tensor):
            out = apply_op(lambda a: fn(a, ax), tensor)
            tensor._bind(out._slot)
            return tensor
        return fn(tensor, ax)
    return tensor


@_instrumented(payload=lambda a: a[1:2])  # the gathered tensor;
def all_gather(tensor_list, tensor, group=None, sync_op=True):
    if _in_trace(tensor.value if isinstance(tensor, Tensor) else tensor):
        ax = _axis_of(group)
        arr = tensor.value if isinstance(tensor, Tensor) else tensor
        g = lax.all_gather(arr, ax)
        n = g.shape[0]
        for i in range(n):
            tensor_list.append(Tensor(g[i]))
        return tensor_list
    tensor_list.append(tensor)
    return tensor_list


@_instrumented
def broadcast(tensor, src=0, group=None, sync_op=True):
    return tensor  # single-controller: every device sees the same program


@_instrumented
def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    # the UNinstrumented all_reduce body: one user call must count as
    # one collective, not as a reduce plus an all_reduce
    return all_reduce.__wrapped__(tensor, op, group, sync_op)


@_instrumented(payload=lambda a: a[1:2])  # the scattered shards
def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    if tensor_list:
        tensor._bind(tensor_list[0]._slot)
    return tensor


@_instrumented(payload=lambda a: a[0:1])  # the input shards
def alltoall(in_tensor_list, out_tensor_list, group=None, sync_op=True):
    out_tensor_list.extend(in_tensor_list)
    return out_tensor_list


@_instrumented(payload=lambda a: a[1:2])  # the reduced shards
def reduce_scatter(tensor, tensor_list, op=ReduceOp.SUM, group=None,
                   sync_op=True):
    if _in_trace(tensor_list[0].value):
        ax = _axis_of(group)
        stacked = jnp.stack([t.value for t in tensor_list])
        out = lax.psum_scatter(stacked, ax, scatter_dimension=0, tiled=False)
        tensor._bind(Tensor(out)._slot)
        return tensor
    tensor._bind(tensor_list[0]._slot)
    return tensor


def send(tensor, dst=0, group=None, sync_op=True):
    raise NotImplementedError(
        "point-to-point send/recv is expressed as lax.ppermute inside "
        "shard_map on TPU (see meta_parallel.pipeline_parallel)")


def recv(tensor, src=0, group=None, sync_op=True):
    raise NotImplementedError(
        "point-to-point send/recv is expressed as lax.ppermute inside "
        "shard_map on TPU (see meta_parallel.pipeline_parallel)")


@_instrumented
def wait(tensor, group=None, use_calc_stream=True):
    if isinstance(tensor, Tensor) and not _in_trace(tensor.value):
        jax.block_until_ready(tensor.value)


def split(x, size, operation, axis=0, num_partitions=1, gather_out=True,
          weight_attr=None, bias_attr=None, name=None):
    """Parity: paddle.distributed.split — model-parallel embedding/linear
    helper. Routes to the meta_parallel layers."""
    from .meta_parallel.parallel_layers.mp_layers import (
        ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding)
    if operation == "embedding":
        layer = VocabParallelEmbedding(size[0], size[1],
                                       weight_attr=weight_attr)
        return layer(x)
    if operation == "linear":
        if axis == 0:
            layer = RowParallelLinear(size[0], size[1],
                                      weight_attr=weight_attr,
                                      has_bias=bias_attr is not False)
        else:
            layer = ColumnParallelLinear(size[0], size[1],
                                         weight_attr=weight_attr,
                                         has_bias=bias_attr is not False,
                                         gather_output=gather_out)
        return layer(x)
    raise ValueError(f"unknown split operation {operation}")
