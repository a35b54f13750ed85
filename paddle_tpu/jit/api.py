"""paddle.jit — trace-and-compile path.

Parity: python/paddle/fluid/dygraph/jit.py + dygraph_to_static/ (the
ProgramTranslator). TPU-native design: instead of AST-rewriting Python into
a ProgramDesc, we *trace* Layer.forward into a jaxpr via a functional view
of the layer (params pytree -> outputs) and hand it to jax.jit — XLA is the
graph program. Python control flow over tensors must use paddle.static.nn
cond/while_loop (lax-backed) exactly as the reference requires graph ops.

`functional_call(layer, params, args)` is the keystone: it temporarily
binds traced arrays into the layer's Parameters so the ordinary eager
forward runs under trace, with the tape disabled (jax.grad provides
differentiation on this path).
"""
import collections
import contextlib
import functools
import threading
import time

import numpy as np
import jax
import jax.numpy as jnp

from ..framework.core import Tensor, Parameter, no_grad, _Slot
from ..framework.random import rng_scope, split_key
from ..framework import fault_injection as _fault
from ..profiler import statistic as _stat
from ..profiler import monitor as _monitor
from ..profiler import cost as _cost
from ..profiler import flight_recorder as _flight
from ..profiler import compile_observatory as _observatory
from ..profiler import dist_observatory as _dobs
from ..profiler import mem_observatory as _mobs
from .deferred import DeferredLoss
from . import warm as _warm

__all__ = ["functional_call", "to_static", "TrainStep", "not_to_static",
           "aot_compile", "count_train_use", "export_step_metrics",
           "DeferredLoss", "HealthMonitorMixin",
           "CheckpointSnapshotMixin"]

# Tracing binds tracer values into SHARED layer state (_bind swaps
# Parameter slots, dy2static swaps layer.forward, aux-loss records live
# on sublayers) — so two programs over one model must not LOWER
# concurrently, or each trace would read the other's tracers. The warm
# pipeline (jit/warm.py) therefore serializes the trace/lower phase
# under this lock; it costs almost nothing (lowering is GIL-bound
# Python anyway) while the expensive XLA compiles overlap freely on the
# background workers. RLock: a traced forward may re-enter
# functional_call (nested functional layers).
_trace_lock = threading.RLock()


def aot_compile(jitted, args, tag=None, static=None, arg_names=None):
    """Explicitly lower + compile a jax.jit function for `args` — the
    AOT dispatch path TrainStep/HybridTrainStep use instead of jax.jit's
    implicit first-call compile. This is the telemetry keystone: the
    trace/lower and XLA-compile phases get separate host spans
    ("jit.trace_lower", "jit.compile"), the persistent compile cache
    (framework/compile_cache.py) hit/miss is observed (hit = compile
    added no new on-disk entry), and the returned executable exposes
    cost_analysis() for free — no re-lower, no re-compile.

    `tag` names the executable in the flight recorder's registry, so a
    crash/hang debug bundle (profiler/flight_recorder.py) carries its
    HLO text + cost analysis. It is also the compilation observatory's
    key (profiler/compile_observatory.py): every call lands one
    `kind:"compile"` ledger record (lower/compile split, cache hit, HLO
    instruction/fusion counts, bytes/flops, peak-memory estimate), and
    a tag recompiling under a NEW abstract signature emits a structured
    retrace event naming the argument that changed — BEFORE the
    recompile runs, so even a hung compile leaves the diagnosis.

    `static` declares values baked into the traced program rather than
    passed as arrays (run_steps' `n`, accumulate's `k`): they are part
    of the observatory signature so a static-value retrace is named as
    such. `arg_names` labels positional args in forensics output.

    Returns (compiled, info) where info carries lower_s / compile_s /
    cache_hit / flops / bytes. The global jit.* metrics count every
    compile; a train-step object's retraces/compile_s counters advance
    via `count_train_use` only when the executable first runs a
    training step, so inspection compiles (compiled_text / flops on an
    untrained signature) can't fake shape instability.
    """
    from ..framework import compile_cache as _cc
    obs_tag = tag or "aot"
    sig = _observatory.abstract_signature(args, static=static)
    sig_key, _ = _observatory.compile_started(obs_tag, sig,
                                              arg_names=arg_names)
    t0 = time.perf_counter()
    _stat.begin_span("jit.trace_lower")
    try:
        # tracing mutates shared layer state — serialize the lower
        # phase across the warm executor's workers; the XLA compile
        # below runs unlocked (GIL-released C++) and overlaps freely
        with _trace_lock:
            lowered = jitted.lower(*args)
    finally:
        lower_s = _stat.end_span()
    _stat.begin_span("jit.compile")
    try:
        # hit/miss attributed per compile via jax's own per-thread
        # cache events — exact even with concurrent compiles, where a
        # bare entry-set diff would blame one compile's new on-disk
        # entry on another's window
        with _cc.observe_compile() as obs:
            compiled = lowered.compile()
    finally:
        compile_s = _stat.end_span()
    cache_hit = obs.cache_on and obs.cache_hit
    added = obs.entries_added
    total = time.perf_counter() - t0
    _monitor.counter("jit.retraces").inc()
    _monitor.counter("jit.cache_hit" if cache_hit
                     else "jit.cache_miss").inc()
    _monitor.histogram("jit.compile_s").observe(total)
    ca = _cost.cost_analysis(compiled)
    info = {"lower_s": lower_s, "compile_s": compile_s,
            "cache_hit": cache_hit,
            "flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0))}
    if tag:  # debug bundles dump this executable's HLO + cost analysis
        _flight.register_executable(tag, compiled)
    _observatory.record_compile(
        obs_tag, sig, sig_key, lower_s, compile_s, cache_hit, compiled,
        cost=ca, arg_names=arg_names, cache_entries_added=len(added))
    return compiled, info


def _step_arg_names(n_batch):
    """Forensics labels for the train-step call signature every
    TrainStep/HybridTrainStep program flavor shares (`_prep` builds the
    matching arg tuple): a retrace event says "batch1: dtype ..."
    instead of "arg8"."""
    return ("params", "opt_state", "scaler_state", "buffers", "key",
            "lr", "step") + tuple(f"batch{i}" for i in range(n_batch))


def count_train_use(owner, info):
    """Fold a compiled executable's cost into the owner's
    retraces/compile_s/last_compile_s the FIRST time it runs a training
    step (idempotent per executable)."""
    if info.get("counted"):
        return
    info["counted"] = True
    total = info["lower_s"] + info["compile_s"]
    owner.retraces += 1
    owner.compile_s += total
    owner.last_compile_s = total


def export_step_metrics(step, dispatch_s, info, compiled_now):
    """Per-step telemetry for a train-step object: step-time histogram,
    cost-analysis FLOPs/MFU gauges, and — when PADDLE_TPU_METRICS_FILE
    is set — one documented JSONL step record
    (tools/check_metrics_schema.py validates the shape).

    step_time_s is the wall time since the previous step's dispatch
    returned: under async dispatch the call itself returns early, but in
    a steady train loop the inter-dispatch interval converges on the
    true device step time. The first (or a recompiling) step falls back
    to its own dispatch time minus the compile."""
    now = time.perf_counter()
    prev = getattr(step, "_last_step_end", None)
    step._last_step_end = now
    compile_s = info["lower_s"] + info["compile_s"] if compiled_now \
        else 0.0
    steady = prev is not None and not compiled_now
    step_time = now - prev if steady \
        else max(dispatch_s - compile_s, 0.0)
    flops = float(info.get("flops", 0.0))
    # MFU only from the steady inter-dispatch interval: the fallback
    # dispatch time is near zero under async dispatch and would publish
    # an absurd >1 utilization for the first/recompiling step
    m = _cost.mfu(flops, step_time) if steady else 0.0
    _monitor.histogram("train.step_s").observe(step_time)
    _monitor.gauge("train.flops_per_step").set(flops)
    _monitor.gauge("train.bytes_per_step").set(
        float(info.get("bytes", 0.0)))
    _monitor.gauge("train.mfu").set(m)
    # export_step always runs: file or no file, the record lands in the
    # flight-recorder ring so a debug bundle carries the step tail
    from .. import device as _device
    rec = {
        "step": int(step._step_i),
        "step_time_s": float(step_time),
        "compile_s": float(compile_s),
        "cache_hit": bool((not compiled_now) or info["cache_hit"]),
        "peak_bytes": int(_device.max_memory_allocated()),
        "flops": flops,
        "mfu": float(m)}
    # fused-epilogue cost split: epilogue_bytes is the ANALYTIC HBM
    # traffic of the two update passes (ops/pallas/fused_update.py
    # bytes_per_step); epilogue_share relates it to the executable's
    # cost_analysis bytes (clamped — interpret-mode cost analysis counts
    # kernel loop bodies once).
    eb = int(getattr(step, "_epilogue_bytes", 0) or 0)
    if eb:
        total_b = float(info.get("bytes", 0.0))
        share = min(eb / total_b, 1.0) if total_b > 0 else 0.0
        rec["epilogue_bytes"] = eb
        rec["epilogue_share"] = float(share)
        _monitor.gauge("train.epilogue_share").set(float(share))
    _monitor.export_step(rec)
    # periodic per-rank skew telemetry (kind:"rankstat") — one int
    # modulo off-cadence; emission + the rank-0 peer gather run only at
    # the cadence boundary, never per step
    _dobs.maybe_rankstat(int(step._step_i))
    # periodic device-memory attribution (kind:"memory") — same cadence
    # shape: first step always, then every PADDLE_TPU_MEMORY_EVERY-th
    _mobs.maybe_memory(int(step._step_i), source="train")


def state_arrays(layer):
    """(param_dict, buffer_dict) of raw jax arrays."""
    params = {k: p.value for k, p in layer.named_parameters()}
    buffers = {k: b.value for k, b in layer.named_buffers()}
    return params, buffers


def epilogue_leaf_meta(model, optimizer, params):
    """Per-leaf epilogue metadata from the model's Parameters + the
    optimizer config: need_clip (ClipGradByGlobalNorm opt-out), lr_scale
    (Parameter.optimize_attr), decay-applies (AdamW
    apply_decay_param_fun, keyed by the flat tree name). Returns (meta,
    need_clip_tree, decay_mask_tree, lr_scale_tree) — the tree views are
    None when trivial, so the default config keeps the historical tree
    numerics bit-for-bit; fused and tree paths both consume the SAME
    tables, which is what keeps them numerically equal."""
    named = dict(model.named_parameters())
    meta = {}
    for k in params:
        p = named.get(k)
        attr = getattr(p, "optimize_attr", None)
        meta[k] = {
            "need_clip": bool(getattr(p, "need_clip", True)),
            "lr_scale": float(attr.get("learning_rate", 1.0)) if attr
            else 1.0,
            "decay": bool(optimizer._decay_applies_name(k)),
        }
    nc = {k: m["need_clip"] for k, m in meta.items()}
    dm = {k: m["decay"] for k, m in meta.items()}
    ls = {k: m["lr_scale"] for k, m in meta.items()}
    return (meta,
            None if all(nc.values()) else nc,
            None if all(dm.values()) else dm,
            None if all(v == 1.0 for v in ls.values()) else ls)


def _bind(layer, arrays):
    """Temporarily swap tensor values; returns restore list."""
    saved = []
    named = dict(layer.named_parameters())
    named.update(dict(layer.named_buffers()))
    for k, arr in arrays.items():
        t = named.get(k)
        if t is None:
            continue
        saved.append((t, t._slot))
        t._slot = _Slot(arr)
    return saved


def _restore(saved):
    for t, slot in saved:
        t._slot = slot


def functional_call(layer, params, buffers, args, kwargs=None, rng_key=None,
                    training=None, convert=False):
    """Run layer.forward with the given arrays bound — pure w.r.t. inputs.

    convert=True routes forward through dy2static first, so plain Python
    control flow over tensors lowers onto lax under the trace (the
    to_static / jit.save path)."""
    kwargs = kwargs or {}
    arrays = dict(params)
    arrays.update(buffers)
    conv_prev, conv_had, conv_set = None, False, False
    saved = []
    prev_training = layer.training
    try:
        if convert:
            import types as _types
            from .dy2static import convert_to_static
            # convert may name the specific decorated method (e.g. a
            # @to_static `predict`); True means the layer's forward
            fwd = convert if callable(convert) and convert is not True \
                else type(layer).forward
            # @to_static on the method itself leaves a StaticFunction as
            # the class attribute — unwrap to the underlying function
            if isinstance(fwd, StaticFunction):
                fwd = fwd._obj
            conv = convert_to_static(fwd)
            conv_had = "forward" in layer.__dict__
            conv_prev = layer.__dict__.get("forward")
            layer.__dict__["forward"] = _types.MethodType(conv, layer)
            conv_set = True
        saved = _bind(layer, arrays)
        if training is not None:
            layer.train() if training else layer.eval()
        wrapped_args = [Tensor(a) if not isinstance(a, Tensor) else a
                        for a in args]
        with no_grad():
            if rng_key is not None:
                with rng_scope(rng_key):
                    out = layer(*wrapped_args, **kwargs)
            else:
                out = layer(*wrapped_args, **kwargs)
    finally:
        _restore(saved)
        layer.train() if prev_training else layer.eval()
        if conv_set:
            if conv_had:
                layer.__dict__["forward"] = conv_prev
            else:
                layer.__dict__.pop("forward", None)
    return jax.tree.map(
        lambda t: t.value if isinstance(t, Tensor) else t, out,
        is_leaf=lambda t: isinstance(t, Tensor))


def not_to_static(fn):
    fn._not_to_static = True
    return fn


def reset_aux_losses(model):
    """Drop any stale per-layer auxiliary-loss and step-counter records
    (e.g. a tracer leaked from a previous trace) before a fresh forward."""
    for layer in model.sublayers(include_self=True):
        if hasattr(layer, "_last_aux"):
            layer._last_aux = None
        if hasattr(layer, "_step_counters"):
            layer._step_counters = None


def take_step_counters(model):
    """(names, vector) of the in-graph step counters that sublayers
    recorded during the forward just run under the CURRENT trace, or
    None. A layer records by setting `_step_counters` to one small
    integer vector whose entries its `step_counter_names` names (e.g.
    incubate.moe.DroplessMoE's routing load); layers that record the same
    names are summed. The records are cleared, so a container that runs
    sublayers under jax.checkpoint or lax.scan calls this INSIDE that
    trace, returns the vector from it, and records the sum itself."""
    found = {}
    for layer in model.sublayers(include_self=True):
        vec = getattr(layer, "_step_counters", None)
        if vec is None:
            continue
        layer._step_counters = None
        names = tuple(layer.step_counter_names)
        found[names] = vec if names not in found else found[names] + vec
    if not found:
        return None
    return (sum(found, ()), jnp.concatenate(list(found.values())))


def collect_aux_losses(model):
    """Sum of `aux_loss_weight * aux` over sublayers that recorded an
    auxiliary loss during the forward just run under the CURRENT trace
    (MoE load-balancing etc.). Returns None when there is none."""
    total = None
    for layer in model.sublayers(include_self=True):
        aux = getattr(layer, "_last_aux", None)
        w = getattr(layer, "aux_loss_weight", 0.0)
        if aux is not None and w:
            a = aux.value if isinstance(aux, Tensor) else aux
            term = w * a
            total = term if total is None else total + term
    return total


class StaticFunction:
    """Compiled wrapper around a Layer or a Tensor function.
    Parity: TranslatedLayer / StaticFunction in the reference."""

    def __init__(self, obj, input_spec=None, build_strategy=None,
                 training=None, method_fn=None):
        self._obj = obj
        self._input_spec = input_spec
        self._training = training
        self._cache = {}
        # when bound via the descriptor protocol: the specific decorated
        # method (may not be `forward`) the compile must execute
        self._method_fn = method_fn
        from ..nn.layer.layers import Layer
        self._is_layer = isinstance(obj, Layer)

    def _sig(self, arrays):
        return tuple((tuple(a.shape), str(a.dtype)) for a in arrays)

    def _compile(self, sig, example_args):
        from .dy2static import convert_to_static
        if self._is_layer:
            layer = self._obj
            training = layer.training if self._training is None \
                else self._training

            # dy2static: convert the forward's Python control flow so
            # tensor-dependent if/while lowers onto lax under the trace
            # (falls back to the original on unsupported constructs)
            conv_target = self._method_fn if self._method_fn is not None \
                else True

            def pure(params, buffers, key, *xs):
                return functional_call(layer, params, buffers, xs,
                                       rng_key=key, training=training,
                                       convert=conv_target)
            jitted = jax.jit(pure)
        else:
            fn = convert_to_static(self._obj)

            def pure(key, *xs):
                with no_grad(), rng_scope(key):
                    out = fn(*[Tensor(x) for x in xs])
                return jax.tree.map(
                    lambda t: t.value if isinstance(t, Tensor) else t, out,
                    is_leaf=lambda t: isinstance(t, Tensor))
            jitted = jax.jit(pure)
        self._cache[sig] = jitted
        return jitted

    def __call__(self, *args, **kwargs):
        from ..framework.core import apply_op, is_grad_enabled
        arrays = [a.value if isinstance(a, Tensor) else jnp.asarray(a)
                  for a in args]
        sig = self._sig(arrays)
        jitted = self._cache.get(sig)
        new_program = jitted is None
        if new_program:
            jitted = self._compile(sig, arrays)
            _monitor.counter("jit.retraces").inc()
        key = split_key()
        # jax.jit compiles lazily on a new program's first dispatch; the
        # call is trace+compile (dispatch returns right after compile
        # under async execution)
        first_call = _stat.span("jit.compile") if new_program \
            else contextlib.nullcontext()
        if self._is_layer:
            named = list(self._obj.named_parameters())
            buffers = {k: b.value for k, b in self._obj.named_buffers()}
            # train-through-to_static (reference StaticFunction records
            # grads): when the tape is live, run the jitted program AS a
            # taped op over the Parameters + inputs so loss.backward()
            # reaches them; jax.vjp differentiates through jax.jit
            if is_grad_enabled() and any(
                    not p.stop_gradient for _, p in named):
                names = [k for k, _ in named]
                n = len(names)

                def fn(*flat, _names=tuple(names), _n=n, _j=jitted,
                       _b=buffers, _k=key):
                    pd = dict(zip(_names, flat[:_n]))
                    return _j(pd, _b, _k, *flat[_n:])

                tensor_args = [a if isinstance(a, Tensor) else Tensor(a)
                               for a in args]
                return apply_op(fn, *[p for _, p in named], *tensor_args)
            params = {k: p.value for k, p in named}
            with first_call:
                out = jitted(params, buffers, key, *arrays)
        else:
            with first_call:
                out = jitted(key, *arrays)
        return jax.tree.map(Tensor, out)

    def __get__(self, instance, owner=None):
        """Descriptor protocol: `@to_static` directly on a method (the
        reference's most common idiom) must bind like a method. Accessed
        through an instance we return a per-layer StaticFunction that
        compiles through the functional layer path."""
        if instance is None:
            return self
        name = getattr(self._obj, "__name__", "forward")
        key = f"_jit_static_{name}"
        bound = instance.__dict__.get(key)
        if bound is None:
            bound = StaticFunction(instance, self._input_spec, None,
                                   self._training, method_fn=self._obj)
            instance.__dict__[key] = bound
            if name == "forward":  # jit.save looks here for spec inference
                instance.__dict__["_jit_static_forward"] = bound
        return bound

    # Layer-protocol passthroughs so a converted layer still acts like one
    def __getattr__(self, name):
        return getattr(self._obj, name)

    @property
    def forward(self):
        return self.__call__

    def concrete_program(self):
        return self._cache

    @property
    def wrapped(self):
        return self._obj


def to_static(layer_or_function=None, input_spec=None, build_strategy=None,
              backend=None, **kwargs):
    """paddle.jit.to_static: decorator or call. Compiles via jax.jit."""
    def wrap(obj):
        if getattr(obj, "_not_to_static", False):
            return obj
        return StaticFunction(obj, input_spec, build_strategy)
    if layer_or_function is None:
        return wrap
    return wrap(layer_or_function)


class HealthMonitorMixin:
    """Host half of the in-graph training-health observatory, shared by
    TrainStep and HybridTrainStep (`monitor_health=True`).

    The in-graph half appends `_health_vec` — ONE tiny f32 vector of
    [loss, grad_norm, param_norm, update_ratio, found_inf] — to the
    already-compiled step. The host half here starts an async D2H copy
    at dispatch and folds vectors into the detectors only once they have
    LANDED (is_ready-gated): zero new host syncs on the hot path.
    `flush_health()` is the blocking drain (epoch end, tests)."""

    def _init_health(self, monitor_health):
        self.monitor_health = bool(monitor_health)
        self._health_pending = collections.deque()
        self._counters_pending = collections.deque()
        self.last_health = None
        if self.monitor_health:
            from ..profiler.health import AnomalyDetector
            self.anomalies = AnomalyDetector()
        else:
            self.anomalies = None

    def _health_vec(self, loss, aux):
        """[loss, grad_norm, param_norm, update_ratio, found_inf] as ONE
        f32 device vector, computed under the trace (monitor_health=True
        appends this to the compiled step). `aux` is `_finish`'s
        epilogue by-product dict: the grad norm is computed ONCE per
        step (shared with the clip factor and — via the GradScaler or
        non-finiteness — found_inf), never as a second tree traversal;
        the fused epilogue's pass-2 kernels supply param/update sums as
        per-chunk side accumulators."""
        grad_norm = aux["grad_norm"]
        # found_inf preference order: the GradScaler's exact flag, then
        # the epilogue's full-tree non-finite sweep (covers leaves a
        # need_clip mask keeps out of the norm), then norm finiteness
        found = aux.get("found_inf")
        if found is None:
            found = aux.get("nonfinite")
        found_inf = found.astype(jnp.float32) if found is not None \
            else (~jnp.isfinite(grad_norm)).astype(jnp.float32)
        param_norm = jnp.sqrt(aux["param_sumsq"])
        update_ratio = jnp.sqrt(aux["update_sumsq"]) / jnp.maximum(
            param_norm, 1e-12)
        return jnp.stack([loss.astype(jnp.float32).reshape(()), grad_norm,
                          param_norm, update_ratio, found_inf])

    @staticmethod
    def _tree_health_aux(aux, params, new_params):
        """Fill aux's param/update sums for a TREE-layout epilogue (the
        fused path's kernels produce them as side outputs instead)."""
        def sumsq(tree):
            leaves = [jnp.sum(jnp.square(l.astype(jnp.float32)))
                      for l in jax.tree.leaves(tree)]
            total = leaves[0] if leaves else jnp.zeros((), jnp.float32)
            for l in leaves[1:]:
                total = total + l
            return total

        aux["param_sumsq"] = sumsq(new_params)
        delta = jax.tree.map(
            lambda n, o: n.astype(jnp.float32) - o.astype(jnp.float32),
            new_params, params)
        aux["update_sumsq"] = sumsq(delta)
        return aux

    def _queue_health(self, step_i, vec):
        """Start the async D2H copy of one step's health vector, then
        fold any vectors that have ALREADY landed into the detectors.
        Never blocks the step loop — resolution is is_ready-gated;
        `flush_health()` is the blocking drain."""
        try:
            vec.copy_to_host_async()
        except (AttributeError, RuntimeError):
            pass  # non-jax array or backend without async copy
        self._health_pending.append((step_i, vec))
        self._drain_health(block=False)

    def _drain_health(self, block):
        while self._health_pending:
            step_i, vec = self._health_pending[0]
            if not block:
                ready = getattr(vec, "is_ready", None)
                if ready is not None and not ready():
                    return  # still computing/copying: check next step
            self._health_pending.popleft()
            self._observe_health(step_i, vec)

    def _observe_health(self, step_i, vec):
        vals = [float(v) for v in np.asarray(vec)]  # hot-sync-ok: vector already landed (is_ready-gated or explicit flush)
        h = dict(zip(("loss", "grad_norm", "param_norm", "update_ratio",
                      "found_inf"), vals))
        self.last_health = {"step": int(step_i), **h}
        _monitor.gauge("health.grad_norm").set(h["grad_norm"])
        _monitor.gauge("health.update_ratio").set(h["update_ratio"])
        # JSONL strictness: a bare NaN token is not valid JSON — export
        # non-finite values as their repr strings (the anomaly event
        # carries the signal; tools/check_metrics_schema.py accepts both)
        import math as _math
        rec = {k: (v if _math.isfinite(v) else repr(v))
               for k, v in h.items()}
        rec["step"] = int(step_i)
        _monitor.export_step(rec, kind="health")
        if self.anomalies is not None:
            self.anomalies.observe(step_i, h, retraces=self.retraces)

    # -- in-graph step counters (take_step_counters) ---------------------
    def _queue_step_counters(self, names, vec):
        """The health queue's pattern for the model's own counter vector:
        start the D2H copy, fold vectors that have LANDED into
        profiler.monitor counters of their names; never a host wait."""
        try:
            vec.copy_to_host_async()
        except (AttributeError, RuntimeError):
            pass
        self._counters_pending.append((names, vec))
        self.flush_step_counters(block=False)

    def flush_step_counters(self, block=True):
        """Fold the pending step-counter vectors into profiler.monitor
        (`block=False`: only those already on the host)."""
        while self._counters_pending:
            names, vec = self._counters_pending[0]
            ready = getattr(vec, "is_ready", None)
            if not block and ready is not None and not ready():
                return
            self._counters_pending.popleft()
            for name, v in zip(names, np.asarray(vec)):  # hot-sync-ok: vector already landed (is_ready-gated or explicit flush)
                _monitor.counter(name).inc(int(v))

    def flush_health(self):
        """Blocking drain of the pending health vectors (epoch end,
        shutdown, tests). Returns the most recent resolved health dict
        (`{"step", "loss", "grad_norm", "param_norm", "update_ratio",
        "found_inf"}`) or None when monitor_health is off / no step ran."""
        self._drain_health(block=True)
        return self.last_health


class CheckpointSnapshotMixin:
    """The checkpoint surface TrainStep and HybridTrainStep share —
    what `distributed.checkpoint.CheckpointManager` saves and restores.

    `tree_state()` is the canonical state tree: per-leaf params and
    optimizer-state VIEWS plus the GradScaler's jit state ({} when no
    scaler rides the step). `snapshot_state()` returns ON-DEVICE buffer
    copies of that tree: the copies are dispatched asynchronously (the
    host returns immediately) and are detached from the donated
    buffers, so the step loop can keep dispatching while the
    checkpoint writer streams the snapshot to disk — the core of the
    snapshot-then-write save path (docs/FAULT_TOLERANCE.md). The
    restore inverse is `set_tree_state` (layout-aware on both the
    fused-flat-store and hybrid-sharded layouts) plus a `scaler_state`
    assignment."""

    def tree_state(self):
        return {"params": self.params,
                "opt_state": dict(self.opt_state),
                "scaler_state": self.scaler_state}

    def snapshot_state(self):
        return jax.tree.map(jnp.copy, self.tree_state())


def fire_step_faults(step_obj, batch):
    """The `train.step` fault-injection site every train-step dispatch
    passes through (framework/fault_injection.py): hard actions
    (kill-at-step-k, delay) execute inside fire(); the soft `nan`
    action is implemented here by NaN-filling the first floating batch
    leaf, so the whole gradient goes non-finite (the GradScaler /
    health path must catch it); the soft `oom` action arms a flag the
    dispatch raises as a synthetic RESOURCE_EXHAUSTED from inside its
    real try-block, so the memory observatory's forensics path runs
    end-to-end. Returns the (possibly poisoned) batch."""
    acts = _fault.fire("train.step")
    if not acts:
        return batch
    if "oom" in acts:
        step_obj._oom_fault = True
    if "nan" not in acts:
        return batch
    out = list(batch)
    for i, b in enumerate(out):
        v = b.value if isinstance(b, Tensor) else jnp.asarray(b)
        if jnp.issubdtype(v.dtype, jnp.floating):
            poisoned = jnp.full_like(v, jnp.nan)
            out[i] = Tensor(poisoned) if isinstance(b, Tensor) \
                else poisoned
            return tuple(out)
    raise ValueError(
        "nan@train.step fault needs at least one floating-point batch "
        "input to poison (integer-id models: inject at the loss level "
        "or use a float-input model in the drill)")


class TrainStep(HealthMonitorMixin, CheckpointSnapshotMixin):
    """One fully-jitted training step: forward + loss + grads + optimizer.

    The TPU-native analogue of the reference's whole-program executor path:
    everything — including the optimizer update and (with `scaler=`) the
    GradScaler's dynamic loss scaling — is a single XLA computation;
    parameter, optimizer-state, and scaler-state buffers are DONATED so
    XLA aliases input/output buffers and updates in place in HBM instead
    of holding a second full copy of the model per step.

        step = TrainStep(model, loss_fn, optimizer)
        loss = step(x, y)          # DeferredLoss: dispatch returns early
        float(loss)                # first host read blocks (recorded)
        step.sync_to_model()       # copy back into Parameters when needed

    The returned loss is a `DeferredLoss` (still a Tensor): the host only
    blocks when the value is actually read, so a steady train loop issues
    step k+1 while step k computes. `accumulate(k, ...)` folds k
    microbatches into one scanned update; `run_steps(n, ...)` scans whole
    optimizer steps.

    Compile observability (the warm-start contract the persistent compile
    cache in framework/compile_cache.py is measured by):
        step.retraces        # how many distinct programs were compiled
        step.compile_s       # total seconds spent tracing+compiling
        step.last_compile_s  # the most recent compile, seconds
    """

    def __init__(self, model, loss_fn, optimizer, mesh=None,
                 in_shardings=None, donate=True, model_returns_loss=False,
                 scaler=None, monitor_health=False, fused_update=None):
        """model_returns_loss=True: the model's forward(*batch) IS the
        scalar loss (e.g. GPTForCausalLM.fused_loss via a wrapper) —
        loss_fn is ignored. Lets memory-fused loss formulations (chunked
        vocab xent) run under the same jitted step.

        scaler: an amp.GradScaler whose dynamic loss scaling runs INSIDE
        the compiled step (scaled loss, unscale, found_inf update skip,
        scale adaptation) with its state donated alongside params.

        monitor_health=True: the compiled step additionally computes the
        training-health scalars — loss, global grad norm, param norm,
        update ratio, found_inf — INSIDE the already-fused XLA program
        (a handful of reductions next to terms XLA already computes) and
        returns them as one tiny f32 vector on the DeferredLoss-style
        async path: the host starts a D2H copy at dispatch and folds the
        vector into `self.anomalies` (profiler/health.AnomalyDetector)
        only once it has LANDED (is_ready-gated — zero new host syncs on
        the hot path; `flush_health()` is the blocking drain). Each
        resolved step also exports a `kind:"health"` metrics record.
        Donation and GradScaler semantics are unchanged.

        fused_update: run the optimizer epilogue as the fused
        multi-tensor Pallas kernels over dtype-bucketed flat buffers
        (ops/pallas/fused_update.py) instead of the per-leaf tree op
        chain. Default (None) reads PADDLE_TPU_FUSED_UPDATE (on unless
        "0") and silently falls back to the tree path when the
        optimizer/clip config has no fused mapping (Lars, RMSProp,
        per-leaf ClipGradByNorm, stochastic rounding). Both paths are
        numerically equal (tests/test_fused_update.py); params and
        opt_state remain visible as per-leaf tree VIEWS either way."""
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.scaler = scaler
        self._model_returns_loss = model_returns_loss
        params, self.buffers = state_arrays(model)
        # params are donated every step; take a private copy so the
        # model's own Parameters stay valid for eager use
        params = jax.tree.map(jnp.array, params)
        self._collect_leaf_meta(model, optimizer, params)
        self._fused = self._build_fused(params, fused_update)
        if self._fused is not None:
            self._params_store, self._opt_store = self._fused.init_stores(
                params, optimizer._multi_precision)
        else:
            self._params_store = params
            self._opt_store = jax.tree.map(
                lambda v: self.optimizer.init_leaf_state(v), params,
                is_leaf=lambda x: hasattr(x, "dtype"))
        # an empty dict is a valid (leafless) donated pytree when no
        # scaler rides along, keeping one step_fn signature
        self.scaler_state = scaler.init_jit_state() if scaler is not None \
            else {}
        # memory-observatory attribution: the stores are donated and
        # REPLACED every step, so register getters (weakref to self)
        # that read the current trees at report time
        _mobs.register("params",
                       self, lambda s: jax.tree.leaves(s._params_store))
        _mobs.register("opt_state",
                       self, lambda s: jax.tree.leaves(s._opt_store))
        self._oom_fault = False
        self._step_i = 0
        self._mesh = mesh
        self.retraces = 0
        self.compile_s = 0.0
        self.last_compile_s = None
        self._init_health(monitor_health)
        if self._fused is not None:
            from ..nn.clip import ClipGradByGlobalNorm
            self._epilogue_bytes = self._fused.bytes_per_step(
                scaling=scaler is not None and scaler.is_enable(),
                need_norm=bool(monitor_health) or isinstance(
                    optimizer._grad_clip, ClipGradByGlobalNorm),
                master_keys=set(self._opt_store["masters"]))

        def step_fn(params, opt_state, scaler_state, buffers, key, lr,
                    step_i, *batch):
            loss, grads = jax.value_and_grad(
                lambda ps: self._objective(ps, scaler_state, buffers, key,
                                           batch))(params)
            loss, new_params, new_state, new_scaler, _ = self._finish(
                loss, grads, params, opt_state, scaler_state, lr, step_i)
            return loss, new_params, new_state, new_scaler

        def step_fn_single(params, opt_state, scaler_state, buffers, key,
                           lr, step_i, *batch):
            """The per-step program: the plain step, plus the health
            vector (monitor_health) and the model's own counter vector
            where a layer records one — a model that records none gives
            the auxiliary output no leaf, and the program is the plain
            one."""
            def objective(ps):
                l = self._objective(ps, scaler_state, buffers, key, batch)
                counters = take_step_counters(self.model)
                self._counter_names = counters[0] if counters else ()
                return l, counters[1] if counters else None

            (loss, counters), grads = jax.value_and_grad(
                objective, has_aux=True)(params)
            out_loss, new_params, new_state, new_scaler, aux = \
                self._finish(loss, grads, params, opt_state, scaler_state,
                             lr, step_i, want_health=self.monitor_health)
            out = (new_params, new_state, new_scaler)
            if self.monitor_health:
                out = (self._health_vec(out_loss, aux),) + out
            return (out_loss,) + out + (() if counters is None
                                        else (counters,))

        donate_argnums = (0, 1, 2) if donate else ()
        self._donate = donate
        self._counter_names = ()
        # the plain flavor stays: run_steps scans it (the scanned path
        # keeps the 4-tuple carry; health and the counters ride the
        # per-step programs)
        self._step_fn = step_fn
        self._jitted = jax.jit(step_fn_single, donate_argnums=donate_argnums)
        # AOT executables keyed by batch signature (aot_compile): phases
        # timed, persistent-cache hit observed, cost_analysis free
        self._exec = {}
        self._scan_jit = {}
        self._acc_jit = {}

    # -- fused epilogue plumbing ----------------------------------------
    def _collect_leaf_meta(self, model, optimizer, params):
        (self._leaf_meta, self._need_clip_tree, self._decay_mask_tree,
         self._lr_scale_tree) = epilogue_leaf_meta(model, optimizer,
                                                   params)

    def _build_fused(self, params, fused_update):
        """The fused multi-tensor epilogue for this (optimizer, clip,
        params) config, or None -> per-leaf tree path. Explicit
        fused_update=True/False wins over PADDLE_TPU_FUSED_UPDATE."""
        import os
        if fused_update is None:
            fused_update = os.environ.get(
                "PADDLE_TPU_FUSED_UPDATE", "1") != "0"
        if not fused_update or not params:
            return None
        spec = self.optimizer.fused_spec()
        if spec is None:
            return None
        from ..nn.clip import ClipGradByGlobalNorm, ClipGradByValue
        clip = self.optimizer._grad_clip
        if clip is not None and not isinstance(
                clip, (ClipGradByGlobalNorm, ClipGradByValue)):
            return None
        if not all(jnp.issubdtype(v.dtype, jnp.floating)
                   for v in jax.tree.leaves(params)):
            return None
        from ..ops.pallas.fused_update import BucketLayout, FusedEpilogue
        layout = BucketLayout(
            [(k, v.shape, v.dtype) for k, v in params.items()],
            meta=self._leaf_meta)
        return FusedEpilogue(layout, spec)

    @property
    def params(self):
        """Per-leaf {name: array} view of the step's parameters. On the
        fused path the donated truth lives in dtype-bucketed flat
        buffers (`_params_store`); this view slices them back out."""
        if self._fused is not None:
            return self._fused.layout.unpack(self._params_store)
        return self._params_store

    @property
    def opt_state(self):
        """Per-leaf optimizer-state view ({name: tuple | {"master",
        "state"}}), state_dict-compatible on both epilogue layouts. The
        tree path gives a dict; the fused path a read-only Mapping
        (fused_update.LeafStateView) that slices a leaf out of the flat
        buffers when it is read, since all leaves at once are a second
        optimizer state on the device. `dict(step.opt_state)` is the
        same plain tree on both paths; the Mapping itself is a pytree
        node of its own, not a dict's treedef."""
        if self._fused is not None:
            return self._fused.lazy_state_view(self._opt_store)
        return self._opt_store

    def set_tree_state(self, params=None, opt_state=None):
        """Load per-leaf state back into the step (checkpoint restore:
        distributed/checkpoint.load_train_state) — the layout-aware
        inverse of the `params`/`opt_state` views, packing into the
        donated flat stores on the fused path."""
        if params is not None:
            self._params_store = self._fused.layout.pack(params) \
                if self._fused is not None \
                else {k: jnp.asarray(v) for k, v in params.items()}
        if opt_state is not None:
            self._opt_store = self._fused.pack_opt_tree(opt_state) \
                if self._fused is not None else opt_state

    # -- traced pieces (shared by __call__ / run_steps / accumulate) -----
    def _loss_of(self, ps, buffers, key, batch):
        """Scalar training loss of one (micro)batch under the trace."""
        model, loss_fn = self.model, self.loss_fn
        reset_aux_losses(model)
        if self._model_returns_loss:
            out = functional_call(model, ps, buffers, batch,
                                  rng_key=key, training=True)
            l = out.value if isinstance(out, Tensor) else out
        else:
            out = functional_call(model, ps, buffers, batch[:-1],
                                  rng_key=key, training=True)
            tgt = Tensor(batch[-1])
            loss_t = loss_fn(
                out if isinstance(out, Tensor) else Tensor(out), tgt)
            l = loss_t.value if isinstance(loss_t, Tensor) else loss_t
        aux = collect_aux_losses(model)
        return l if aux is None else l + aux.astype(l.dtype)

    def _objective(self, ps, scaler_state, buffers, key, batch):
        """The differentiated quantity: the loss, scaled when a
        GradScaler rides inside the step. `ps` is the donated parameter
        store — on the fused path the dtype-bucketed flat buffers, whose
        per-leaf views the forward consumes (differentiating THROUGH the
        unpack makes the gradients arrive already bucketed: the VJP
        packs leaf cotangents with one concatenate per bucket)."""
        if self._fused is not None:
            ps = self._fused.layout.unpack(ps)
        l = self._loss_of(ps, buffers, key, batch)
        if self.scaler is not None and self.scaler.is_enable():
            return l.astype(jnp.float32) * scaler_state["scale"]
        return l

    def _finish(self, loss, grads, params, opt_state, scaler_state, lr,
                step_i, want_health=False):
        """From (possibly scaled) loss + grads to the updated carry: one
        unscale/scale-adaptation, one clip, ONE optimizer update —
        whether the grads came from one batch or a scanned accumulation
        of k microbatches. Returns (loss, new_params, new_state,
        new_scaler_state, aux); aux carries the epilogue's shared
        by-products — the ONE global grad norm (clip factor, health
        grad_norm) and found_inf — plus the health sums when
        want_health.

        Fused path: two Pallas passes over the flat buffers
        (ops/pallas/fused_update.py). Tree path: the per-leaf reference
        shape, with the grad norm computed ONCE and threaded to both
        the clip and the health vector instead of per-consumer."""
        scaler = self.scaler
        clip = self.optimizer._grad_clip
        if self._fused is not None:
            if scaler is not None and scaler.is_enable():
                loss = loss / scaler_state["scale"]
            new_params, new_state, new_scaler_state, aux = \
                self._fused.finish(
                    grads, params, opt_state, lr, step_i, scaler=scaler,
                    scaler_state=scaler_state, clip=clip,
                    with_stats=want_health)
            return loss, new_params, new_state, new_scaler_state, aux
        if scaler is not None and scaler.is_enable():
            loss = loss / scaler_state["scale"]
            grads, found_inf, new_scaler_state = \
                scaler.jit_unscale_and_update(scaler_state, grads)
        else:
            found_inf, new_scaler_state = None, scaler_state
        from ..nn.clip import (clip_grads_tree, global_grad_norm,
                               ClipGradByGlobalNorm)
        gn = None
        if want_health or isinstance(clip, ClipGradByGlobalNorm):
            gn = global_grad_norm(grads, self._need_clip_tree)
        grads = clip_grads_tree(grads, clip,
                                need_clip=self._need_clip_tree,
                                global_norm=gn)
        new_params, new_state = self.optimizer.apply_gradients_tree(
            params, grads, opt_state, lr, step_i, found_inf=found_inf,
            decay_mask=self._decay_mask_tree,
            lr_scale=self._lr_scale_tree)
        aux = {"grad_norm": gn, "found_inf": found_inf}
        if want_health:
            self._tree_health_aux(aux, params, new_params)
            if gn is not None:
                nonfin = ~jnp.isfinite(gn)
                if self._need_clip_tree is not None:
                    # leaves a need_clip mask keeps out of the norm must
                    # still trip the health found_inf signal
                    for k, g in grads.items():
                        if not self._need_clip_tree.get(k, True):
                            nonfin = nonfin | jnp.any(~jnp.isfinite(
                                g.astype(jnp.float32)))
                aux["nonfinite"] = nonfin
        return loss, new_params, new_state, new_scaler_state, aux

    def _dispatch(self, cache, sig, make_jitted, args, tag,
                  max_entries=None, static=None, arg_names=None,
                  span=None):
        """The ONE dispatch path every TrainStep program flavor
        (per-step / scanned steps / scanned accumulation) goes through:
        executable-cache lookup with optional LRU bound, AOT compile on
        miss, retrace accounting, timed dispatch. `static`/`arg_names`
        feed the compilation observatory's signature + forensics. `tag`
        names the executable (compile ledger, OOM site, NaN bundle);
        the host span is `span`, or `tag` where none is given (a joined
        or inline compile nests under it as jit.trace_lower /
        jit.compile).

        A miss goes through the warm pipeline's single-flight table
        (jit/warm.py): if `warm()`/`warm_run_steps()`/`warm_accumulate()`
        already has this executable compiling in the background, the
        dispatch JOINS that compile — blocking only on the one
        executable it actually needs, never duplicating the work or the
        ledger record. Returns (outputs, info, compiled_now,
        dispatch_s)."""
        _flight.heartbeat(self._step_i)  # watchdog liveness pulse
        _stat.begin_span(span or tag)
        try:
            entry = cache.get(sig)
            compiled_now = entry is None
            if compiled_now:
                if max_entries and len(cache) >= max_entries:
                    cache.pop(next(iter(cache)))  # bound compile growth
                # inline=True: a dispatch miss compiles on THIS thread
                # when it wins the single-flight race — never queued
                # behind unrelated background warms; if a warm already
                # has this executable in flight, join it instead
                entry = self._warm_submit(
                    cache, sig, make_jitted, tag, args, static=static,
                    arg_names=arg_names, inline=True).result()
            else:  # LRU: re-insert so cycling signatures don't thrash
                cache[sig] = cache.pop(sig)
            compiled, info = entry
            count_train_use(self, info)
            try:
                if getattr(self, "_oom_fault", False):
                    # oom@train.step soft fault: raise the synthetic
                    # exhaustion from INSIDE the real dispatch try so
                    # the forensics below is the tested path
                    self._oom_fault = False
                    raise RuntimeError(
                        "RESOURCE_EXHAUSTED: injected OOM "
                        "(oom@train.step fault): failed to allocate "
                        "request for 8.00GiB on device")
                out = compiled(*args)
            except Exception as e:
                # under jax_debug_nans an AOT executable raises jax's
                # INTERNAL nan error (jax 0.9.0: not a
                # FloatingPointError, not exported) — known by its name,
                # so that `import paddle_tpu.jit` depends on no private
                # jax path
                internal_nan = \
                    type(e).__name__ == "InternalFloatingPointError"
                if not (internal_nan or isinstance(
                        e, (FloatingPointError, RuntimeError))):
                    raise
                if _mobs.is_oom(e):
                    # allocator exhaustion: dump mem_state.json forensics
                    # and re-raise naming the top holders
                    raise _mobs.oom_error(e, site=tag) from e
                # jax_debug_nans (framework.debug.enable_jit_nan_checks)
                # found a non-finite value: flight-record it and write a
                # debug bundle (ring tail + this executable's HLO +
                # all-thread stacks) before re-raising to the caller.
                # With donated buffers the op-level re-run cannot replay
                # (inputs already consumed) and surfaces as a
                # RuntimeError over deleted arrays — same detection,
                # reported as the FloatingPointError it is.
                donated_rerun = (
                    isinstance(e, RuntimeError)
                    and jax.config.jax_debug_nans
                    and "deleted" in str(e))
                if isinstance(e, RuntimeError) and not donated_rerun:
                    raise
                _flight.record_event("nan_detected", where=tag,
                                     step=int(self._step_i),
                                     error=str(e)[:300])
                _flight.dump("nan", exc=e)
                if internal_nan:
                    # jit's own call path would turn it into a
                    # FloatingPointError after an op-by-op re-run; a
                    # compiled executable has no such path — report the
                    # FloatingPointError it is
                    raise FloatingPointError(
                        "jax_debug_nans detected a non-finite value in "
                        f"the compiled {tag} program: {e}") from e
                if donated_rerun:
                    raise FloatingPointError(
                        "jax_debug_nans detected a non-finite value in "
                        f"the compiled {tag} program (the op-level "
                        "re-run could not localize it because the step "
                        "donates its buffers; build with donate=False "
                        "to localize)") from e
                raise
        finally:
            dispatch_s = _stat.end_span()
        return out, info, compiled_now, dispatch_s

    def _prep_run_steps(self, n, batch, data_per_step):
        """(sig, make_jitted, static, arrays) for one scanned-steps
        program — the ONE place run_steps' signature and program factory
        are built, shared by `run_steps` and `warm_run_steps` so a
        warmed executable is exactly the one dispatch will use."""
        arrays = [b.value if isinstance(b, Tensor) else jnp.asarray(b)
                  for b in batch]
        if data_per_step:
            for a in arrays:
                # ndim check first: a 0-d scalar has no shape[0] and must
                # hit this friendly error, not an IndexError
                if a.ndim == 0 or a.shape[0] != n:
                    raise ValueError(
                        f"data_per_step=True needs a leading dim of n={n} "
                        f"on every batch array, got shape {a.shape} — a "
                        "traced gather would silently clamp short arrays "
                        "to their last micro-batch")
        # NOTE: n (and the batch shapes) are static — each distinct
        # signature compiles its own scanned program, kept in a small
        # cache; prefer a fixed segment length plus a per-step tail
        sig = (n, bool(data_per_step),
               tuple((a.shape, str(a.dtype)) for a in arrays))

        def make_jitted():
            step_fn = self._step_fn

            def multi(params, opt_state, scaler_state, buffers, key, lr,
                      base, *arrs):
                def body(carry, i):
                    p, s, sc = carry
                    b = [a[i] for a in arrs] if data_per_step else list(arrs)
                    # step index as f32: `beta ** step` with a traced int
                    # promotes to f64 under x64, breaking the scan carry
                    loss, p, s, sc = step_fn(
                        p, s, sc, buffers, jax.random.fold_in(key, i), lr,
                        (base + i).astype(jnp.float32), *b)
                    return (p, s, sc), loss

                (p, s, sc), losses = jax.lax.scan(
                    body, (params, opt_state, scaler_state),
                    jnp.arange(n, dtype=jnp.int32))
                return losses, p, s, sc

            return jax.jit(
                multi, donate_argnums=(0, 1, 2) if self._donate else ())

        static = {"n": n, "data_per_step": bool(data_per_step)}
        return sig, make_jitted, static, arrays

    def _run_steps_args(self, arrays):
        key = split_key()
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        base = jnp.asarray(self._step_i + 1, jnp.int32)
        return (self._params_store, self._opt_store, self.scaler_state,
                self.buffers, key, lr, base, *arrays)

    def run_steps(self, n, *batch, data_per_step=False):
        """Run `n` optimizer steps in ONE XLA dispatch (lax.scan over the
        step body) and return the per-step losses as a Tensor of shape [n].

        The TPU-native analogue of the reference executor running many
        iterations per `Executor.run` call (ref python/paddle/fluid/
        executor.py): the whole loop lives on device, so per-step host
        dispatch disappears. Best for small/host-bound models. For models
        whose params+optimizer state dominate HBM, per-step `__call__`
        with buffer donation can be faster: XLA double-buffers a while-
        loop carry, where donated per-dispatch buffers update in place
        (measured 3.3x on the 355M-param bench config). With `data_per_step=True` every batch array
        carries a leading `n` dimension holding one micro-batch per step;
        otherwise the same batch is reused each step (benchmarking/
        overfit-sanity loops). The learning rate is frozen at its current
        scheduler value for the scanned segment; call `scheduler.step()`
        between segments for piecewise schedules."""
        sig, make_jitted, static, arrays = self._prep_run_steps(
            n, batch, data_per_step)
        args = self._run_steps_args(arrays)
        out, info, compiled_now, dt = self._dispatch(
            self._scan_jit, sig, make_jitted, args, "train.run_steps",
            max_entries=8, static=static,
            arg_names=_step_arg_names(len(arrays)))
        losses, self._params_store, self._opt_store, \
            self.scaler_state = out
        # telemetry keeps dispatch-only time: the first call's span also
        # covered the compile
        if compiled_now:
            dt = max(dt - (info["lower_s"] + info["compile_s"]), 0.0)
        _monitor.histogram("train.run_steps_s").observe(dt)
        _monitor.export_step(
            {"steps": n,
             "dispatch_s": float(dt),  # hot-sync-ok: host perf counter
             "flops": float(  # hot-sync-ok: python dict value, not device
                 info.get("flops", 0.0))}, kind="scan")
        self._step_i += n
        return Tensor(losses)

    def _make_acc_fn(self, k):
        """The scanned-microbatch accumulation program: k microbatches
        folded with ONE optimizer update (reuses the same traced pieces
        as the per-step path, so GradScaler/clip/donation semantics are
        identical)."""
        def acc_fn(params, opt_state, scaler_state, buffers, key, lr,
                   step_i, *batch):
            def body(carry, xs):
                i, micro = xs[0], xs[1:]
                loss_sum, grads_sum = carry
                l, g = jax.value_and_grad(
                    lambda ps: self._objective(
                        ps, scaler_state, buffers,
                        jax.random.fold_in(key, i), micro))(params)
                return (loss_sum + l.astype(jnp.float32),
                        jax.tree.map(jnp.add, grads_sum, g)), None

            zeros = jax.tree.map(jnp.zeros_like, params)
            (loss_sum, grads), _ = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32), zeros),
                (jnp.arange(k, dtype=jnp.int32), *batch))
            # mean over microbatches: for mean-reduced losses this makes
            # the update numerically identical to one k-times-larger
            # batch (equal microbatch sizes)
            loss = loss_sum / k
            grads = jax.tree.map(lambda g: g / k, grads)
            out_loss, new_params, new_state, new_scaler, aux = \
                self._finish(loss, grads, params, opt_state, scaler_state,
                             lr, step_i, want_health=self.monitor_health)
            if self.monitor_health:
                health = self._health_vec(out_loss, aux)
                return out_loss, health, new_params, new_state, new_scaler
            return out_loss, new_params, new_state, new_scaler
        return acc_fn

    def _prep_accumulate(self, k, batch):
        """(sig, make_jitted, arrays) for one scanned-accumulation
        program — shared by `accumulate` and `warm_accumulate` so the
        warmed executable is exactly the one dispatch will use."""
        arrays = [b.value if isinstance(b, Tensor) else jnp.asarray(b)
                  for b in batch]
        for a in arrays:
            if a.ndim == 0 or a.shape[0] != k:
                raise ValueError(
                    f"accumulate(k={k}) needs a leading microbatch dim of "
                    f"{k} on every batch array, got shape {a.shape}")
        sig = (k, tuple((a.shape, str(a.dtype)) for a in arrays))

        def make_jitted():
            return jax.jit(
                self._make_acc_fn(k),
                donate_argnums=(0, 1, 2) if self._donate else ())

        return sig, make_jitted, arrays

    def accumulate(self, k, *batch):
        """ONE optimizer update from `k` scanned microbatches in ONE XLA
        dispatch. Every batch array carries a leading dim of `k` (one
        microbatch per slot); gradients are averaged across microbatches
        inside the scan, then the usual unscale/clip/update runs exactly
        once — numerics match a single step over the k-times-larger batch
        for mean-reduced losses, with only one microbatch's activations
        live at a time. Params/opt/scaler state stay donated. This is
        what `hapi.Model.fit(accumulate_grad_batches=k)` dispatches."""
        sig, make_jitted, arrays = self._prep_accumulate(k, batch)
        if k == 1:
            return self(*[a[0] for a in arrays])
        self._step_i += 1
        key = split_key()
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        args = (self._params_store, self._opt_store, self.scaler_state,
                self.buffers, key, lr, self._step_i, *arrays)

        out, info, compiled_now, dispatch_s = self._dispatch(
            self._acc_jit, sig, make_jitted, args, "train.accumulate",
            max_entries=8, static={"k": k},
            arg_names=_step_arg_names(len(arrays)))
        if self.monitor_health:
            loss, health, self._params_store, self._opt_store, \
                self.scaler_state = out
            self._queue_health(self._step_i, health)
        else:
            loss, self._params_store, self._opt_store, \
                self.scaler_state = out
        export_step_metrics(self, dispatch_s, info, compiled_now)
        return DeferredLoss(loss)

    def input_sharding(self, arr):
        """Sharding the compiled step expects for a batch leaf — the
        device prefetch ring (io/device_prefetch.py) asks this so H2D
        copies land placed for the step while the previous step computes.
        The single-device step has no placement constraint (None =
        default device)."""
        return None

    def _prep(self, batch, step_i):
        """(sig, full arg tuple) for one dispatch — the ONE place the
        call signature is built: __call__ and the inspection paths must
        agree exactly, because the cached executable bakes the input
        avals."""
        arrays = [b.value if isinstance(b, Tensor) else jnp.asarray(b)
                  for b in batch]
        sig = tuple((tuple(a.shape), str(a.dtype)) for a in arrays)
        args = (self._params_store, self._opt_store, self.scaler_state,
                self.buffers, split_key(),
                jnp.asarray(self.optimizer.get_lr(), jnp.float32),
                step_i, *arrays)
        return sig, args

    # -- background warmup (the compile pipeline, jit/warm.py) -----------
    def _warm_submit(self, cache, sig, make_jitted, tag, args,
                     static=None, arg_names=None, inline=False):
        """Single-flight compile of one executable (warm.submit_cached):
        background for warm() calls, `inline=True` for dispatch-path
        misses (the caller needs this executable NOW and must not queue
        behind unrelated background warms); either way a racer joins
        the one flight, and the entry installs into `cache` before the
        flight closes."""
        return _warm.submit_cached(
            cache, sig, tag,
            lambda: aot_compile(make_jitted(), args, tag=tag,
                                static=static, arg_names=arg_names),
            inline=inline)

    def warm(self, *batch):
        """Start a BACKGROUND AOT compile of the per-step executable for
        exactly this batch signature and return a `jit.warm.WarmHandle`
        — the host keeps doing useful work (building data pipelines,
        warming OTHER executables) while XLA compiles on a worker
        thread; the first `__call__` with this signature joins the
        in-flight compile instead of recompiling. Because the signature
        comes from the same `_prep` as dispatch (same shapes, dtypes,
        shardings, donation), warming adds ZERO executables beyond the
        steady-state set — provable from the compilation observatory's
        ledger. Join a whole warm set with `jit.warm.join(handles)`,
        which also records the wall-vs-sum overlap evidence."""
        sig, args = self._prep(batch, self._step_i + 1)
        return self._warm_submit(self._exec, sig, lambda: self._jitted,
                                 "train.step", args,
                                 arg_names=_step_arg_names(len(batch)))

    def warm_run_steps(self, n, *batch, data_per_step=False):
        """Background-compile the `run_steps(n, ...)` scanned program
        for this signature (see `warm`)."""
        sig, make_jitted, static, arrays = self._prep_run_steps(
            n, batch, data_per_step)
        args = self._run_steps_args(arrays)
        return self._warm_submit(self._scan_jit, sig, make_jitted,
                                 "train.run_steps", args, static=static,
                                 arg_names=_step_arg_names(len(arrays)))

    def warm_accumulate(self, k, *batch):
        """Background-compile the `accumulate(k, ...)` scanned program
        for this signature (see `warm`). k == 1 warms the per-step
        executable, mirroring the dispatch path."""
        sig, make_jitted, arrays = self._prep_accumulate(k, batch)
        if k == 1:
            return self.warm(*[a[0] for a in arrays])
        args = (self._params_store, self._opt_store, self.scaler_state,
                self.buffers, split_key(),
                jnp.asarray(self.optimizer.get_lr(), jnp.float32),
                self._step_i + 1, *arrays)
        return self._warm_submit(self._acc_jit, sig, make_jitted,
                                 "train.accumulate", args,
                                 static={"k": k},
                                 arg_names=_step_arg_names(len(arrays)))

    def __call__(self, *batch):
        """One optimizer step. On the host the call is one `train.step`
        span whose children cover it: `train.step.prep`,
        `train.step.dispatch` and `train.step.telemetry`."""
        self._step_i += 1
        with _stat.span("train.step", step_num=self._step_i):
            with _stat.span("train.step.prep"):
                if _fault.active():  # fault drills only; two dict reads when off
                    batch = fire_step_faults(self, batch)
                sig, args = self._prep(batch, self._step_i)
            out, info, compiled_now, dispatch_s = self._dispatch(
                self._exec, sig, lambda: self._jitted, args, "train.step",
                arg_names=_step_arg_names(len(batch)),
                span="train.step.dispatch")
            loss, *rest = out
            health = rest.pop(0) if self.monitor_health else None
            self._params_store, self._opt_store, self.scaler_state, \
                *extra = rest
            with _stat.span("train.step.telemetry"):
                if health is not None:
                    self._queue_health(self._step_i, health)
                if extra:
                    self._queue_step_counters(self._counter_names, extra[0])
                export_step_metrics(self, dispatch_s, info, compiled_now)
                # non-blocking handle: dispatch has already returned; the
                # host copy streams in the background and resolves on
                # first read
                return DeferredLoss(loss)

    def cost_analysis(self, *batch):
        """XLA's analytical cost report for THIS batch signature's
        per-step executable ({'flops', 'bytes accessed', ...}) — free
        when the step has already run (the AOT executable is cached);
        otherwise compiles it first (warm via the persistent cache)
        without touching the retrace counters."""
        return _cost.cost_analysis(self._executable(*batch))

    def flops(self, *batch):
        """Per-step FLOPs of the compiled executable (0.0 unknown)."""
        return _cost.executable_flops(self._executable(*batch))

    def _executable(self, *batch):
        sig, args = self._prep(batch, self._step_i + 1)
        entry = self._exec.get(sig)
        if entry is None:
            # single-flight with any in-flight warm of this signature
            entry = self._warm_submit(
                self._exec, sig, lambda: self._jitted, "train.step",
                args, arg_names=_step_arg_names(len(batch)),
                inline=True).result()
        return entry[0]

    def sync_to_model(self):
        named = dict(self.model.named_parameters())
        with no_grad():
            for k, v in self.params.items():
                named[k]._slot = _Slot(v)
        if self.scaler is not None and self.scaler_state:
            self.scaler.sync_from_jit_state(self.scaler_state)

    def compiled_text(self, *batch):
        """Optimized HLO of the per-step executable (inspection/tests:
        the donation proof greps input_output_alias entries here).
        Reuses the AOT executable cache — no extra compile after a
        step has run with this signature."""
        return self._executable(*batch).as_text()
