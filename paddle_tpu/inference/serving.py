"""Serving engine: continuous-batching inference over the XLA stack.

Beyond-parity subsystem (the reference's AnalysisPredictor is strictly
one-request-at-a-time): two engines share one scheduler core and tie
together pieces that already exist in-repo — `jit.api.aot_compile` (AOT
executables + the persistent compile cache), `ops.paged_attention.
PagedKVCache` (paged decode state), `models.gpt` paged decode, and the
`profiler.monitor` metrics registry.

**InferenceEngine** — stateless models (classifiers, encoders, anything
`jit.save`-able): callers `submit()` into a bounded queue and get a
`concurrent.futures.Future`; a background dispatcher coalesces
concurrent requests into ONE padded batch along a configurable ladder
of shape buckets (batch rounded up to the ladder, sequence padded to a
bucket), dispatched through an AOT executable compiled once per bucket
— steady-state serving never retraces. Admission control is fast-fail:
a full queue raises `QueueFullError` immediately (callers shed load
instead of timing out), per-request deadlines expire in-queue, and
`drain()`/`shutdown()` finish in-flight work before stopping.

**GenerationEngine** — autoregressive decode over any model exposing
the paged-decode surface (`GPTForCausalLM`, `SSMForCausalLM`) and any
cache strategy behind `inference/cache_strategy.py` (`PagedKVCache` kv
pages, `RecurrentStateCache` fixed-size state slots, `HybridCache`
both): continuous batching in the vLLM/Ragged-Paged-Attention sense
(see PAPERS.md). New requests prefill into free cache slots between
decode steps, every decode step advances ALL in-flight sequences by
one token in a single fixed-shape jitted program (the batch is padded
to a power-of-two bucket with rows that target the reserved pad slot,
so admit/evict never changes the compiled shape), finished sequences
(eos / max_new_tokens) are evicted without stalling their neighbors,
and tokens stream back per request as they are sampled.

Both report into `profiler/monitor`:

    serve.queue_depth   gauge      requests waiting in the queue
    serve.batch_size    histogram  real rows per dispatched batch
    serve.latency_s     histogram  submit -> result, per request
    serve.ttft_s        histogram  submit -> first token (generation)
    serve.requests      counter    accepted requests
    serve.rejected      counter    fast-fail queue-full rejections
    serve.expired       counter    deadline expiries
    serve.pad_tokens    counter    COMPUTE-BEARING padding dispatched
                                   (the ragged path's skipped pad
                                   slots count 0 by construction)
    serve.retraces      counter    bucket executables compiled
    serve.errors        counter    batches/steps failed onto futures
    serve.prefix_hits   counter    prompt tokens served from the
                                   refcounted prefix cache
    serve.shared_pages  gauge      KV pages with more than one holder
    serve.chunked_prefill_tokens counter  prompt tokens admitted via
                                   chunked prefill (ragged steps)
    serve.generated_tokens counter tokens emitted to callers
    serve.goodput_tokens / serve.wasted_tokens counters  generated
                                   tokens split by whether the request
                                   completed or died (expired/
                                   cancelled/errored) — maintained by
                                   profiler/serve_observatory
    serve.tpot_s        histogram  time per output token (decode phase)
    serve.kv_*          gauges     page-pool occupancy snapshots

Every request additionally carries a `profiler.serve_observatory`
RequestTrace — submit/admit/first-token/terminal timestamps, token
counts, prefix-hit tokens, peak pages held — emitted as ONE
`kind:"request"` record at its terminal state (completed / expired /
rejected / error / cancelled), and `GenerationEngine` emits periodic
`kind:"kvcache"` pool snapshots plus `load_report()` (the admission
snapshot a load-aware router consumes). See docs/SERVING.md
"The serving observatory".

The dispatcher and decode loops are fenced by tools/check_no_hot_sync.py:
the ONLY host blocks are the scheduler's queue wait and the one
deliberate device read per batch (marked `# hot-sync-ok:`); sampling
runs ON DEVICE (seeded temperature/top-k/top-p per request via
`SamplingParams`, argmax when temperature is 0) and is collected
through an async copy — int32s cross to the host, never [vocab]-sized
logits.

`GenerationEngine` also speaks the prefill/decode DISAGGREGATION
protocol the serving front door (`paddle_tpu/inference/frontdoor.py`
`ServingRouter`) orchestrates: an engine with a handoff wired
(`set_handoff`) plays the PREFILL role — it chunk-prefills a prompt,
streams the first token, then moves the KV chain to a decode-role
engine via `PagedKVCache.export_chain` / `adopt()` without copying a
page (both engines share one pool; see docs/SERVING.md "The front
door").

With `speculative=SpeculativeConfig(draft_model, k)` the ragged loop
runs SPECULATIVE DECODING (inference/speculative.py, docs/SERVING.md
"Speculative decoding"): a small draft model proposes k tokens per
active sequence per iteration and the target verifies all k+1
positions as ONE prefill-shaped row through the same `serve.
ragged_step` executable — the MIN_Q_TOKENS token-bucket floor means a
k<=7 verify row pads into the signature a 1-token decode row already
warmed, so steady state adds zero executables. Accepted tokens are
bit-identical to the non-speculative stream (position-keyed draws);
rejected tails roll back the KV write cursor only. `kind:"serve"` and
`kind:"request"` records carry `proposed_tokens` / `accepted_tokens`
/ `accept_rate` (zeros on non-speculative paths), and `load_report()`
exposes the engine's cumulative accept rate.
"""
import itertools
import threading
import time
import weakref
from collections import deque
from concurrent.futures import Future, InvalidStateError

import numpy as np
import jax
import jax.numpy as jnp

from ..framework.core import Tensor
from ..profiler import monitor as _monitor
from ..profiler import serve_observatory as _obs
from ..profiler import mem_observatory as _mobs
from ..profiler import statistic as _stat
from .cache_strategy import strategy_of
from .speculative import accept_length

__all__ = ["ServingError", "QueueFullError", "DeadlineExceeded",
           "EngineStopped", "BucketLadder", "InferenceEngine",
           "GenerationEngine", "GenerationHandle", "SamplingParams"]


class SamplingParams:
    """Per-request decode sampling config (`GenerationEngine.submit`/
    `ServingRouter.submit`, ragged path only — the legacy bucketed
    path stays greedy). The defaults ARE today's behavior:
    temperature 0 is the on-device argmax, bit-exact with the
    pre-sampling path.

    temperature > 0 enables seeded on-device sampling; `top_k` keeps
    the k highest logits (None/0 disables), `top_p` keeps the smallest
    nucleus reaching that probability mass (None/1.0 disables), both
    applied before one `jax.random.categorical` draw per token. `seed`
    makes the request reproducible: the per-token key is
    fold_in(PRNGKey(seed), absolute token position), so the sampled
    text does not depend on batching, admit/evict order, or which
    engine of a disaggregated pair decoded it. seed=None draws a
    fresh deterministic-per-process seed at submit."""

    __slots__ = ("temperature", "top_k", "top_p", "seed")

    def __init__(self, temperature=0.0, top_k=None, top_p=None,
                 seed=None):
        self.temperature = float(temperature)
        if self.temperature < 0.0:
            raise ValueError(
                f"temperature must be >= 0, got {temperature}")
        self.top_k = None if not top_k else int(top_k)
        if self.top_k is not None and self.top_k < 1:
            raise ValueError(f"top_k must be >= 1, got {top_k}")
        self.top_p = None if top_p is None else float(top_p)
        if self.top_p is not None and not 0.0 < self.top_p <= 1.0:
            raise ValueError(
                f"top_p must be in (0, 1], got {top_p}")
        self.seed = None if seed is None else int(seed)

    @property
    def greedy(self):
        return self.temperature <= 0.0

    def key_data(self, fallback_seed=0):
        """uint32[2] threefry key data for this request's seed (host
        bit math — no device op at submit). ONE layout source: the
        gpt helper next to the sampler that consumes these keys."""
        from ..models.gpt import sampling_key_data
        seed = self.seed if self.seed is not None else int(fallback_seed)
        return sampling_key_data(seed)

    def __repr__(self):
        return (f"SamplingParams(temperature={self.temperature}, "
                f"top_k={self.top_k}, top_p={self.top_p}, "
                f"seed={self.seed})")


GREEDY = SamplingParams()
# seeds for seed=None sampling requests: deterministic per-process
# submit order, never colliding across engines
_SEED_IDS = itertools.count(1)


class ServingError(RuntimeError):
    """Base class for serving-engine scheduling errors."""


class QueueFullError(ServingError):
    """Fast-fail backpressure: the bounded request queue is full."""


class DeadlineExceeded(ServingError):
    """The request's deadline passed before it was dispatched."""


class EngineStopped(ServingError):
    """submit() after shutdown()/drain() closed the engine."""


class BucketLadder:
    """The shape-bucket ladder: batch sizes round UP to the smallest
    bucket that fits (requests above the top bucket are rejected at
    submit), sequence lengths pad up to the smallest seq bucket. One
    AOT executable per (batch bucket, seq bucket) serves every request
    shape in that cell — the whole point is that steady-state serving
    dispatches only pre-compiled programs."""

    def __init__(self, batch_sizes=(1, 2, 4, 8), seq_buckets=None):
        if not batch_sizes:
            raise ValueError("BucketLadder needs at least one batch size")
        self.batch_sizes = tuple(sorted(set(int(b) for b in batch_sizes)))
        if self.batch_sizes[0] < 1:
            raise ValueError("batch buckets must be >= 1")
        self.seq_buckets = tuple(sorted(set(int(s) for s in seq_buckets))) \
            if seq_buckets else None

    @property
    def max_batch(self):
        return self.batch_sizes[-1]

    def batch(self, n):
        """Smallest batch bucket >= n (None when n exceeds the top)."""
        for b in self.batch_sizes:
            if n <= b:
                return b
        return None

    def seq(self, t):
        """Smallest seq bucket >= t; identity when no seq ladder."""
        if self.seq_buckets is None:
            return t
        for s in self.seq_buckets:
            if t <= s:
                return s
        raise ValueError(
            f"sequence length {t} exceeds the largest seq bucket "
            f"{self.seq_buckets[-1]} — extend the ladder")


class _Request:
    __slots__ = ("arrays", "n", "key", "future", "deadline", "t_submit",
                 "trace")

    def __init__(self, arrays, n, key, deadline, trace=None):
        self.arrays = arrays
        self.n = n
        self.key = key  # coalescing signature, computed once at submit
        self.future = Future()
        self.deadline = deadline
        self.t_submit = time.perf_counter()
        self.trace = trace  # serve_observatory RequestTrace


def _trace_outcome(exc):
    """Map a rejection exception onto a request-record outcome: a
    deadline expiry is "expired", shutdown-shed work is "cancelled"
    (the server chose not to serve it), anything else failed onto the
    future is "error"."""
    if isinstance(exc, DeadlineExceeded):
        return "expired"
    if isinstance(exc, EngineStopped):
        return "cancelled"
    return "error"


def _finish_trace(trace, exc):
    """Close a trace from a rejection path (trace may be None only for
    handles built outside submit — engine paths always attach one)."""
    if trace is not None:
        trace.finish(_trace_outcome(exc),
                     error=f"{type(exc).__name__}: {exc}")


def _resolve_future(fut, value):
    """set_result that tolerates a caller's concurrent cancel(): the
    done() check and the set are not atomic, and a cancelled future
    just means nobody is waiting — never a scheduler-thread error."""
    try:
        fut.set_result(value)
    except InvalidStateError:
        pass


def _reject_future(fut, exc):
    try:
        fut.set_exception(exc)
    except InvalidStateError:
        pass


def _to_ndarray(a):
    """Normalize one request leaf to a host ndarray the ENGINE owns
    (requests are tiny; keeping them host-side makes concat/pad cheap
    and defers the single H2D to the batched dispatch). An ndarray
    input is COPIED: submit() returns before dispatch, and a caller
    reusing its buffer must not mutate a queued request. Device arrays
    and lists already materialize fresh through np.asarray."""
    if isinstance(a, Tensor):
        a = a.value
    if isinstance(a, np.ndarray):
        return a.copy()
    return np.asarray(a)


def _as_jitted(model):
    """Wrap any supported model flavor into a jax.jit-ed function of raw
    arrays (the thing `aot_compile` lowers):

    - a jax.jit wrapper (has .lower): used as-is
    - a jit.save_load.TranslatedLayer: its exported call with the loaded
      params/buffers closed over
    - an nn.Layer: functional_call with a frozen eval-mode snapshot of
      its parameters (rebuild the engine after mutating weights)
    - any plain callable over arrays: jax.jit(fn)
    """
    if hasattr(model, "lower") and callable(model):
        return model
    from ..jit.save_load import TranslatedLayer
    if isinstance(model, TranslatedLayer):
        call = model._call
        if model._meta.get("kind") == "function":
            return jax.jit(lambda *xs: call(*xs))
        # private copies, same reason as the Layer branch below: a
        # later fine-tune step may DONATE the live parameter buffers,
        # which would invalidate every warmed executable's closure
        params = {k: jnp.array(p.value)
                  for k, p in model.named_parameters()}
        buffers = {k: jnp.array(v) for k, v in model._buffers.items()}
        return jax.jit(lambda *xs: call(params, buffers, *xs))
    from ..nn.layer.layers import Layer
    if isinstance(model, Layer):
        from ..jit.api import functional_call, state_arrays
        params, buffers = state_arrays(model)
        # private copies: the engine's executables must stay valid even
        # if the caller later donates/mutates the live Parameters
        params = jax.tree.map(jnp.array, params)
        return jax.jit(lambda *xs: functional_call(
            model, params, buffers, xs, training=False))
    if callable(model):
        return jax.jit(model)
    raise TypeError(f"cannot serve {type(model).__name__}: expected a "
                    "Layer, TranslatedLayer, jitted or plain callable")


_STOP = object()
# serve.* metrics and kind:"serve" records are process-global: the
# per-engine name stamped on each record is what keeps the telemetry of
# multiple engines in one process attributable
_ENGINE_IDS = itertools.count()


def _run_scheduler(ref):
    """Scheduler thread entry. Holds only a WEAKREF to the engine
    between iterations: an engine abandoned without shutdown() becomes
    garbage-collectible (a bound-method target would pin it via the
    thread registry forever), and once collected the thread simply
    exits — no leaked 50 ms-wakeup thread, no leaked parameter
    copies. An exception ESCAPING the loop core would kill this thread
    with callers still parked in Future.result() — the catch-all fails
    all outstanding work loudly instead."""
    while True:
        eng = ref()
        if eng is None:
            return
        try:
            alive = eng._loop_once()
        except BaseException as e:
            eng._scheduler_crashed(e)
            return
        if not alive:
            return
        del eng  # drop the strong ref before the next iteration


class _SchedulerLifecycle:
    """The scheduler core both engines share: stop-the-world admission
    gate (`_stopping`), drain-to-empty, shutdown with optional cancel.
    Subclasses provide `_outstanding()` (any queued OR claimed work?),
    `_take_pending()`/`_take_outstanding()` (detach doomed work UNDER
    the lock) and `_reject_detached()` (reject it OUTSIDE the lock —
    set_exception fires done-callbacks synchronously, and one that
    re-enters the engine would deadlock under `_cv`), and keep
    `_outstanding()` truthful across every lock release — that's the
    whole drain() contract."""

    _paused = False  # engines without pause() still drain through here

    def drain(self, timeout=None):
        """Stop admission, then block until every queued and in-flight
        request has resolved. Returns True when fully drained."""
        end = None if timeout is None else time.monotonic() + timeout
        with self._cv:
            self._stopping = True
            self._paused = False  # a paused engine must still drain
            self._cv.notify_all()
            while self._outstanding():
                left = None if end is None else end - time.monotonic()
                if left is not None and left <= 0:
                    return False
                self._cv.wait(0.05 if left is None else min(left, 0.05))
        return True

    def shutdown(self, wait=True):
        """Drain (wait=True) or cancel pending work (wait=False), then
        stop the scheduler thread. Idempotent; submit() afterwards
        raises EngineStopped."""
        if wait:
            self.drain()
        doomed = []
        with self._cv:
            self._stopping = True
            self._paused = False
            if not wait:
                doomed = self._take_pending()
            self._cv.notify_all()
        # rejections OUTSIDE the lock: set_exception fires done-
        # callbacks synchronously, and one that re-enters the engine
        # would deadlock here (same discipline as _flush_expired)
        self._reject_detached(doomed, EngineStopped("engine shut down"))
        self._thread.join(timeout=10)

    def __del__(self):
        if getattr(self, "_cv", None) is None:
            return  # __init__ raised before the lock existed
        with self._cv:
            self._stopping = True
            # weakrefs were cleared before __del__, so the scheduler
            # thread is exiting (or already gone) and will never claim
            # what's still queued: detach it all and reject below —
            # callers blocked in Future.result() fail loudly instead
            # of hanging forever
            doomed = self._take_outstanding()
            self._cv.notify_all()
        self._reject_detached(
            doomed, EngineStopped("engine abandoned without shutdown()"))

    def _scheduler_crashed(self, exc):
        """Last resort (called by _run_scheduler's catch-all): the loop
        core itself escaped. Fail every outstanding request with the
        cause chained — a silent thread death would hang callers
        forever — and refuse new submits."""
        _monitor.counter("serve.errors").inc()
        # on the flight-recorder timeline + crash bundle: a dead engine
        # mid-traffic is exactly the state the ring is for
        from ..profiler import flight_recorder as _flight
        _flight.record_event("serve_scheduler_crashed",
                             engine=getattr(self, "name", "serve"),
                             type=type(exc).__name__,
                             message=str(exc)[:300])
        _flight.dump("serve_crash", exc=exc)
        err = ServingError(
            "scheduler thread crashed; this engine is dead — rebuild it")
        err.__cause__ = exc
        with self._cv:
            self._stopping = True
            doomed = self._take_outstanding()
            self._cv.notify_all()
        self._reject_detached(doomed, err)


class InferenceEngine(_SchedulerLifecycle):
    """Continuous-batching engine for stateless models.

        engine = InferenceEngine(layer, batch_sizes=(1, 2, 4, 8))
        engine.warm(example)           # one AOT executable per bucket
        fut = engine.submit(x)         # Future; x has a leading batch dim
        y = fut.result()

    Scheduling: a bounded queue (fast-fail `QueueFullError` when full —
    backpressure belongs at admission, not in a timeout) feeds one
    dispatcher thread. The dispatcher pops the oldest request, waits up
    to `max_wait_ms` to coalesce more SAME-SIGNATURE requests (same
    dtype / trailing shape after seq bucketing) up to the top batch
    bucket, pads the fused batch to the ladder, and runs ONE executable.
    Results come back as host ndarrays sliced per request — the single
    device read per batch is the engine's only hot-path sync.

    Requests whose deadline (`submit(..., deadline_ms=)`) passes while
    queued fail with `DeadlineExceeded` instead of wasting a bucket
    slot. `drain()` stops admission and finishes everything in flight;
    `shutdown()` drains (or cancels, `wait=False`) and joins the
    thread. `pause()`/`resume()` hold dispatch — a scheduling hook for
    tests and for atomically swapping warmed executables.

    NOTE on ragged traffic: with `seq_buckets=None` (the default) every
    NOVEL sequence length lazily compiles — and retains — one more
    executable per batch bucket, stalling that batch for the compile.
    Fixed-shape workloads are fine; for variable-length inputs always
    set a seq ladder so the executable set stays bounded."""

    def __init__(self, model, batch_sizes=(1, 2, 4, 8), seq_buckets=None,
                 seq_axis=1, max_queue=64, max_wait_ms=2.0, pad_value=0,
                 pipeline=2, name=None):
        self.name = name or f"infer{next(_ENGINE_IDS)}"
        self.ladder = BucketLadder(batch_sizes, seq_buckets)
        self.seq_axis = int(seq_axis)
        self.max_queue = int(max_queue)
        self.max_wait_s = float(max_wait_ms) / 1000.0
        self.pad_value = pad_value
        # pipeline: batches in flight on the device before the
        # dispatcher blocks reading the oldest result — XLA executes
        # batch k while the dispatcher coalesces and dispatches k+1, so
        # scheduler overhead hides under device compute (1 = fully
        # synchronous; 2 is the sweet spot, mirroring the train-side
        # prefetch ring depth)
        self.pipeline = max(1, int(pipeline))
        self._jitted = _as_jitted(model)
        self._exec = {}          # sig -> (compiled, info)
        self._compile_lock = threading.Lock()  # warm() vs lazy dispatch
        self.retraces = 0        # bucket executables compiled (AOT or lazy)
        self._buf = deque()
        self._cv = threading.Condition()
        self._stopping = False   # no new submits
        self._paused = False
        self._inflight = 0       # requests claimed but not yet resolved
        self._expired_reqs = deque()  # deferred rejections (dispatcher)
        self._pending_results = deque()  # dispatched, awaiting resolution
        _obs.register_engine(self)  # debug bundles snapshot load_report
        self._thread = threading.Thread(
            target=_run_scheduler, args=(weakref.ref(self),),
            name="serve-dispatch", daemon=True)
        self._thread.start()

    # -- admission -------------------------------------------------------
    def submit(self, *args, deadline_ms=None):
        """Enqueue one request (every arg carries a leading batch dim,
        all args the same) and return its Future. The Future resolves to
        the model output(s) as host ndarrays sliced to this request's
        rows. Raises QueueFullError / EngineStopped immediately; a
        deadline_ms that expires in-queue fails the Future with
        DeadlineExceeded."""
        arrays = [_to_ndarray(a) for a in args]
        if not arrays:
            raise ValueError("submit() needs at least one input array")
        n = int(arrays[0].shape[0]) if arrays[0].ndim else 0
        for a in arrays:
            if a.ndim == 0 or a.shape[0] != n:
                raise ValueError(
                    "every input must carry the same leading batch dim; "
                    f"got {[tuple(x.shape) for x in arrays]}")
        if n < 1 or self.ladder.batch(n) is None:
            raise ValueError(
                f"request batch {n} does not fit the ladder "
                f"{self.ladder.batch_sizes} (max "
                f"{self.ladder.max_batch} rows per request)")
        # the coalescing key doubles as validation: an over-bucket seq
        # length raises HERE, at the caller — discovered at dispatch it
        # would raise inside the scheduler thread and kill it for all
        key = self._key_of(arrays)
        deadline = None if deadline_ms is None else \
            time.perf_counter() + float(deadline_ms) / 1000.0
        trace = _obs.start_request(
            self.name, rows=n,
            deadline_s=None if deadline_ms is None
            else float(deadline_ms) / 1000.0)
        req = _Request(arrays, n, key, deadline, trace=trace)
        reject = None
        with self._cv:
            if self._stopping:
                reject = EngineStopped("engine is drained/shut down")
            elif len(self._buf) >= self.max_queue:
                _monitor.counter("serve.rejected").inc()
                reject = QueueFullError(
                    f"serving queue full ({self.max_queue} waiting) — "
                    "shed load or raise max_queue")
            else:
                self._buf.append(req)
                _monitor.counter("serve.requests").inc()
                _monitor.gauge("serve.queue_depth").set(len(self._buf))
                self._cv.notify_all()
        if reject is not None:
            # trace close OUTSIDE the lock: finish() appends to the
            # metrics JSONL, and file I/O must never stall the engine
            trace.finish("rejected", error=str(reject))
            raise reject
        return req.future

    def __call__(self, *args, deadline_ms=None, timeout=None):
        """Synchronous convenience: submit + result."""
        return self.submit(*args, deadline_ms=deadline_ms).result(timeout)

    # -- warmup ----------------------------------------------------------
    def warm(self, *example):
        """AOT-compile one executable per batch bucket for this
        example's signature (trailing shape/dtype after seq bucketing;
        the example's own leading dim is ignored) — CONCURRENTLY, on the
        background compile executor (jit/warm.py): the ladder's buckets
        are independent programs, so the warm set's wall clock is
        roughly the slowest single compile, not the sum (one
        `kind:"warm"` metrics record carries the wall-vs-sum evidence).
        Blocks until every bucket is ready; `warm_async` is the
        non-blocking variant. Returns the number of executables
        compiled NOW — already-warm buckets are free, and with the
        persistent compile cache (PR 1) even a fresh process reloads
        instead of recompiling. Call once per distinct input signature
        before serving; steady state then never retraces."""
        from ..jit import warm as _warm
        handles = self.warm_async(*example)
        _warm.join(handles)
        return sum(1 for h in handles if h.fresh)

    def warm_async(self, *example):
        """Submit one background AOT compile per batch bucket and
        return the list of `jit.warm.WarmHandle`s WITHOUT blocking —
        serving can start immediately (a request for a still-compiling
        bucket joins its flight), and the caller can overlap its own
        startup work with the compiles. Join with
        `jit.warm.join(handles)` for the warm-set overlap record."""
        arrays = [_to_ndarray(a) for a in example]
        return [self._submit_bucket(self._bucket_specs(arrays, b))
                for b in self.ladder.batch_sizes]

    def _submit_bucket(self, specs, inline=False):
        """Single-flight compile of one bucket's executable
        (jit/warm.py submit_cached); an already-compiled bucket returns
        an instantly-done handle. `inline=True` is the lazy-dispatch
        path: compile on the calling thread rather than queue behind
        the other buckets' background warms."""
        from ..jit import warm as _warm
        from ..jit.api import aot_compile
        sig = self._sig(specs)
        # tag: debug bundles dump this bucket's HLO + cost analysis
        # (flight recorder executable registry)
        bucket = specs[0].shape[0] if specs else 0
        tag = f"serve.{self.name}.batch{bucket}"

        def thunk():
            return aot_compile(self._jitted, tuple(specs), tag=tag,
                               arg_names=tuple(
                                   f"input{i}"
                                   for i in range(len(specs))))

        def install(entry):
            # runs before the flight closes: the bookkeeping must count
            # each bucket exactly once even when warm() raced a lazy
            # dispatch
            with self._compile_lock:
                if sig not in self._exec:
                    self._exec[sig] = entry
                    self.retraces += 1
                    _monitor.counter("serve.retraces").inc()

        return _warm.submit_cached(self._exec, sig, tag, thunk,  # lint-ok[unlocked-shared-state]: GIL-atomic attribute load passes the dict reference; membership changes stay under _compile_lock in install
                                   install=install, inline=inline)

    def _bucket_specs(self, arrays, b):
        """ShapeDtypeStructs of the padded batch for bucket b."""
        specs = []
        for a in arrays:
            shape = list(a.shape)
            shape[0] = b
            if a.ndim > self.seq_axis:
                shape[self.seq_axis] = self.ladder.seq(
                    shape[self.seq_axis])
            specs.append(jax.ShapeDtypeStruct(tuple(shape), a.dtype))
        return specs

    @staticmethod
    def _sig(specs):
        return tuple((tuple(s.shape), str(s.dtype)) for s in specs)

    def _ensure_compiled(self, specs):
        """(executable entry, compiled_now). The warm pipeline's
        single-flight table replaces the old big compile lock: a lazy
        dispatch racing warm() (or another dispatch) JOINS the one
        in-flight compile — blocking only on the bucket it needs while
        other buckets keep compiling concurrently."""
        sig = self._sig(specs)
        entry = self._exec.get(sig)
        if entry is not None:
            return entry, False
        handle = self._submit_bucket(specs, inline=True)
        return handle.result(), handle.fresh

    # -- scheduler core --------------------------------------------------
    def _key_of(self, arrays):
        """Coalescing key: requests fuse only when their padded trailing
        shapes and dtypes agree (the batch dim is the ladder's job).
        Computed ONCE at submit — the dispatcher's queue scans compare
        stored tuples instead of rebuilding shapes under the lock."""
        parts = []
        for a in arrays:
            shape = list(a.shape[1:])
            if a.ndim > self.seq_axis:
                shape[self.seq_axis - 1] = self.ladder.seq(
                    a.shape[self.seq_axis])
            parts.append((tuple(shape), str(a.dtype)))
        return tuple(parts)

    def _expired(self, req, now):
        """Drop a dead request. Runs UNDER self._cv — the rejection is
        deferred to _flush_expired (outside the lock) because
        set_exception fires done-callbacks synchronously, and a
        callback that re-enters the engine would deadlock here."""
        if req.future.cancelled():
            # a cancelled future occupies no bucket row; it still rides
            # _expired_reqs so its request trace closes outside the
            # lock (outcome "cancelled")
            self._expired_reqs.append(("cancelled", req))
            return True
        if req.deadline is not None and now > req.deadline:
            # outcome decided HERE, with the counter: a caller cancel
            # racing the deferred flush must not file this deadline
            # miss as "cancelled" while serve.expired already counted it
            _monitor.counter("serve.expired").inc()
            self._expired_reqs.append(("expired", req))
            return True
        return False

    def _flush_expired(self):
        """Reject deferred deadline expiries (and close cancelled
        requests' traces). Dispatcher thread only, never holding
        self._cv. Outcomes were fixed at triage time (_expired) —
        rejecting an already-cancelled future is a tolerated no-op."""
        while self._expired_reqs:
            outcome, req = self._expired_reqs.popleft()
            if outcome == "expired":
                _reject_future(req.future, DeadlineExceeded(
                    "deadline passed before dispatch"))
            if req.trace is not None:
                req.trace.finish(outcome)

    def _take_batch(self, block=True):
        """Pop the oldest live request, then coalesce same-signature
        followers up to the top batch bucket, waiting at most max_wait_s
        for stragglers. Returns a non-empty list; _STOP when shutting
        down with nothing left; None when the queue is idle and
        block=False (the dispatcher has results to resolve instead)."""
        with self._cv:
            while True:
                if self._stopping and not self._buf:
                    return _STOP
                if self._paused or not self._buf:
                    if not block:
                        return None
                    self._cv.wait(0.05)  # the scheduler's one legit block
                    if self._paused or not self._buf:
                        # still idle: hand control back so the runner
                        # drops its strong ref (GC-ability of abandoned
                        # engines depends on this bound wait)
                        return None
                    continue
                first = self._buf.popleft()
                now = time.perf_counter()
                if self._expired(first, now):
                    # hand control back so the dispatcher rejects the
                    # deferred expiry OUTSIDE the lock before blocking
                    return None
                key = first.key
                # counted the instant it leaves the queue: the
                # coalescing wait below RELEASES the lock, and drain()
                # must never observe "queue empty, nothing in flight"
                # while claimed requests sit in this local batch
                self._inflight += 1
                batch, rows = [first], first.n
                t_end = now + self.max_wait_s
                while rows < self.ladder.max_batch:
                    got = self._scan_matching(batch, rows, key)
                    rows += got
                    if rows >= self.ladder.max_batch:
                        break
                    left = t_end - time.perf_counter()
                    if left <= 0:
                        break
                    self._cv.wait(left)  # coalescing window
                _monitor.gauge("serve.queue_depth").set(len(self._buf))
                return batch

    def _scan_matching(self, batch, rows, key):
        """Move queued same-key requests into `batch` (expiring dead
        ones on the way); returns rows added. Holds self._cv."""
        added, keep, now = 0, deque(), time.perf_counter()
        while self._buf:
            r = self._buf.popleft()
            if self._expired(r, now):
                continue
            if r.key == key \
                    and rows + added + r.n <= self.ladder.max_batch:
                batch.append(r)
                added += r.n
                self._inflight += 1  # claimed: see _take_batch
            else:
                keep.append(r)
        self._buf.extend(keep)  # emptied above: order preserved
        return added

    def _loop_once(self):
        """One scheduler iteration (False = thread exits): coalesce/
        dispatch up to `pipeline` batches onto the device before
        blocking on the oldest result — XLA computes batch k while
        Python pads, compiles and dispatches k+1 (the serving twin of
        the training prefetch ring)."""
        pending = self._pending_results  # (batch, out, meta)
        batch = self._take_batch(block=not pending)
        self._flush_expired()  # outside the lock: callbacks may re-enter
        if batch is not None and batch is not _STOP:
            try:
                pending.append(self._dispatch_batch(batch))
            except Exception as e:  # engine survives a bad batch
                self._fail_batch(batch, e)
        if pending and (batch is None or batch is _STOP
                        or len(pending) >= self.pipeline):
            done = pending.popleft()
            try:
                self._resolve_batch(*done)
            except Exception as e:
                self._fail_batch(done[0], e)
        return not (batch is _STOP and not pending)

    def _fail_batch(self, batch, exc):
        _monitor.counter("serve.errors").inc()
        for r in batch:
            _reject_future(r.future, exc)
            _finish_trace(r.trace, exc)
        with self._cv:
            self._inflight -= len(batch)
            self._cv.notify_all()

    def _dispatch_batch(self, batch):
        """Pad + fuse the coalesced requests and dispatch the bucket's
        executable ASYNCHRONOUSLY — returns (batch, device outputs,
        meta) for _resolve_batch; nothing here blocks on the device."""
        for r in batch:  # claimed by the dispatcher: queue phase over
            if r.trace is not None:
                r.trace.admitted()
        rows = sum(r.n for r in batch)
        b = self.ladder.batch(rows)
        cols, pad_elems = [], 0
        for j in range(len(batch[0].arrays)):
            parts = []
            for r in batch:
                a = r.arrays[j]
                if a.ndim > self.seq_axis:
                    s = self.ladder.seq(a.shape[self.seq_axis])
                    if s != a.shape[self.seq_axis]:
                        pad = [(0, 0)] * a.ndim
                        pad[self.seq_axis] = (0, s - a.shape[self.seq_axis])
                        pad_elems += (s - a.shape[self.seq_axis]) * \
                            (a.size // max(a.shape[self.seq_axis], 1))
                        a = np.pad(a, pad, constant_values=self.pad_value)
                parts.append(a)
            col = np.concatenate(parts, axis=0) if len(parts) > 1 \
                else parts[0]
            if b > rows:
                fill = np.full((b - rows,) + col.shape[1:], self.pad_value,
                               col.dtype)
                pad_elems += fill.size
                col = np.concatenate([col, fill], axis=0)
            cols.append(col)
        # un-warmed bucket: compiled lazily (counted) and kept
        entry, _ = self._ensure_compiled(
            [jax.ShapeDtypeStruct(c.shape, c.dtype) for c in cols])
        compiled, _ = entry
        _stat.begin_span("serve.batch")
        try:
            out = compiled(*cols)  # async dispatch: returns immediately
        finally:
            _stat.end_span()
        _monitor.histogram("serve.batch_size").observe(rows)
        _monitor.counter("serve.pad_tokens").inc(int(pad_elems))
        return batch, out, (rows, b, pad_elems)

    def _resolve_batch(self, batch, out, meta):
        """Block on one dispatched batch's outputs (the engine's ONE
        deliberate device read), slice per request, resolve futures."""
        rows, b, pad_elems = meta
        outs = list(out) if isinstance(out, (list, tuple)) else [out]
        host = [np.asarray(o) for o in outs]  # hot-sync-ok: batch result read
        for h in host:
            if h.ndim == 0 or h.shape[0] != b:
                # a model whose outputs don't carry the leading batch
                # dim cannot be sliced per request — fail LOUDLY rather
                # than hand each caller a slice of the wrong axis
                raise ValueError(
                    f"model output shape {h.shape} does not carry the "
                    f"batch dim (expected leading {b}); the engine can "
                    "only serve batch-leading outputs")
        single = not isinstance(out, (list, tuple))
        now = time.perf_counter()
        off = 0
        lat_sum = 0.0
        # a view into the padded batch would pin the whole bucket-sized
        # host array for as long as any caller retains its result: copy
        # per request, except when one request IS the whole batch
        share = len(batch) == 1 and batch[0].n == b
        for r in batch:
            sl = [h[off:off + r.n] if share else h[off:off + r.n].copy()
                  for h in host]
            off += r.n
            lat = now - r.t_submit
            lat_sum += lat
            _monitor.histogram("serve.latency_s").observe(lat)
            if r.trace is not None:  # record exists before result lands
                # a caller may have cancelled AFTER dispatch: the
                # set_result below is then a no-op, and the ledger must
                # not claim a completion nobody received
                r.trace.finish("cancelled" if r.future.cancelled()
                               else "completed")
            _resolve_future(r.future, sl[0] if single else sl)
        with self._cv:
            self._inflight -= len(batch)
            self._cv.notify_all()
        _monitor.export_step(
            {"engine": self.name, "requests": len(batch),
             "batch_size": rows, "bucket_batch": b,
             "queue_depth": len(self._buf), "pad_tokens": int(pad_elems),  # lint-ok[unlocked-shared-state]: GIL-atomic len() for telemetry; the deque object is never replaced, staleness is one request
             "latency_s": lat_sum / len(batch)}, kind="serve")

    # -- lifecycle -------------------------------------------------------
    def pause(self):
        """Hold dispatch (queued requests wait; submits still accepted)."""
        with self._cv:
            self._paused = True

    def resume(self):
        with self._cv:
            self._paused = False
            self._cv.notify_all()

    def _outstanding(self):
        # _expired_reqs counts: those futures are still unresolved
        # until the dispatcher's next _flush_expired, and drain()
        # promises "every queued request has resolved"
        return bool(self._buf or self._inflight or self._expired_reqs)

    def _take_pending(self):
        """Detach the queued, never-claimed requests (under self._cv);
        the caller rejects them outside the lock."""
        out = list(self._buf)
        self._buf.clear()
        return out

    def _take_outstanding(self):
        # _take_pending plus the work only the (dead) scheduler thread
        # could have resolved: deferred expiries and dispatched-but-
        # unresolved batches
        out = self._take_pending()
        out.extend(self._expired_reqs)
        self._expired_reqs.clear()
        while self._pending_results:
            out.extend(self._pending_results.popleft()[0])
        return out

    def _reject_detached(self, reqs, exc):
        for r in reqs:
            _reject_future(r.future, exc)
            _finish_trace(r.trace, exc)

    def load_report(self):
        """Instantaneous admission snapshot (the serving observatory's
        router interface — docs/SERVING.md): queue depth vs capacity,
        claimed-but-unresolved work, compiled buckets, and recent tail
        latency from the process-global histograms. Pure host reads.
        The lock acquire is BOUNDED: debug bundles call this to
        diagnose a hung engine, and a scheduler wedged holding _cv
        must not hang the hang-diagnosis tool."""
        if not self._cv.acquire(timeout=1.0):
            return {"engine": self.name,
                    "unavailable": "engine lock held > 1s (wedged?)"}
        try:
            q = len(self._buf)
            inflight = self._inflight
            stopping = self._stopping
        finally:
            self._cv.release()
        lat = _monitor.get_metric("serve.latency_s")
        return {
            "engine": self.name, "stopping": stopping,
            "queue_depth": q, "max_queue": self.max_queue,
            "inflight": inflight, "pipeline": self.pipeline,
            "buckets_compiled": len(self._exec),
            "latency_p50_s": lat.percentile(50) if lat else 0.0,
            "latency_p99_s": lat.percentile(99) if lat else 0.0,
        }

    def observatory_snapshot(self):
        """What a debug bundle records for this engine
        (serve_observatory.debug_payload)."""
        return {"load_report": self.load_report()}


# ---------------------------------------------------------------------------
# Generation: continuous batching over the paged KV cache
# ---------------------------------------------------------------------------

_GEN_END = object()


class GenerationHandle:
    """Per-request view of an in-flight generation: `tokens()` streams
    token ids as the decode loop produces them; `result()` blocks for
    the full generated sequence (np.int64 array, prompt excluded)."""

    def __init__(self, prompt, max_new_tokens, eos_token_id):
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.eos_token_id = eos_token_id
        self.future = Future()
        self._stream = deque()
        self._cv = threading.Condition()
        self._closed = False
        self.t_submit = time.perf_counter()
        self.deadline = None  # perf_counter bound (submit deadline_ms=)
        self.deadline_ms = None  # the submit-time value, verbatim (a
        # router's handoff record re-derives the SLO class from THIS,
        # not from the time remaining — one request, one class)
        self.trace = None     # serve_observatory RequestTrace
        self.sampling = GREEDY  # SamplingParams (submit sampling=)
        self.key = None         # uint32[2] per-request base PRNG key
        self.request_id = None  # stable id (the trace id), stamped in
        # engine submit BEFORE the enqueue: rides the handle, the
        # exported KVChainHandle, and the adopted decode trace, so
        # route + both request records + the journey join
        self.router = None      # ServingRouter name (fleet telemetry),
        # stamped in engine submit via the router= kwarg — never after
        # the scheduler can already be acting on the request

    def _push(self, tok):
        with self._cv:
            self._stream.append(tok)
            self._cv.notify_all()

    def _close(self):
        with self._cv:
            self._closed = True
            self._cv.notify_all()

    def tokens(self):
        """Iterator of token ids, yielding each as soon as it is
        decoded; ends when the sequence finishes (or its error is
        raised)."""
        while True:
            with self._cv:
                while not self._stream and not self._closed:
                    self._cv.wait(0.05)
                if self._stream:
                    tok = self._stream.popleft()
                else:
                    break
            yield tok
        # a CANCELLED stream just ends (nobody is waiting for more) —
        # and Future.exception() would RAISE CancelledError here, not
        # return it, so the guard is load-bearing
        exc = self.future.exception() \
            if self.future.done() and not self.future.cancelled() else None
        if exc is not None:
            raise exc

    def result(self, timeout=None):
        return self.future.result(timeout)


class _ActiveSeq:
    __slots__ = ("sid", "handle", "generated", "last", "reserve",
                 "cached", "filled", "sampling", "key", "draft_sid",
                 "dlen")

    def __init__(self, sid, handle, reserve, cached=0):
        self.sid = sid
        self.handle = handle
        self.generated = []
        self.last = None
        self.reserve = reserve  # worst-case pages this request may draw
        self.cached = cached    # prompt tokens served by the prefix cache
        self.filled = cached    # prompt tokens whose KV is in the pool
        self.sampling = handle.sampling  # SamplingParams
        self.key = handle.key            # uint32[2] base PRNG key
        # speculative decoding (inference/speculative.py): the DRAFT
        # cache's twin sequence id (None = this request decodes
        # non-speculatively) and the draft's committed KV length — an
        # independent cursor over the SAME token history, because the
        # draft computes KV for prompt tokens the target served from
        # its prefix cache
        self.draft_sid = None
        self.dlen = 0


class GenerationEngine(_SchedulerLifecycle):
    """Continuous-batching autoregressive serving over a shared decode
    cache — any strategy behind the `inference/cache_strategy.py`
    interface: a `PagedKVCache` of kv pages (attention models), a
    `RecurrentStateCache` of fixed-size state slots (SSM models,
    models/ssm.py — O(1) admission cost per sequence), or a
    `HybridCache` pairing both for interleaved SSM/attention stacks.
    The engine never branches on the strategy: admission, planning,
    telemetry, and handoff all go through the cache's own ledger.

        engine = GenerationEngine(model, n_pages=256, max_batch=8,
                                  eos_token_id=50256)
        h = engine.submit(prompt_ids, max_new_tokens=64)
        for tok in h.tokens(): ...      # streamed as decoded
        full = h.result()               # np.int64 [n_generated]

    With `ragged=True` (the default whenever the model implements
    `paged_ragged_step` — GPTForCausalLM, SSMForCausalLM) every scheduler iteration
    runs ONE jitted step over the Pallas ragged kernel
    (ops/pallas/paged_attention.py) carrying mixed rows: each active
    sequence's decode token AND up to `prefill_chunk` tokens of queued
    prompts — so a long prompt admits incrementally (CHUNKED PREFILL)
    instead of monopolizing the loop, and pad slots cost zero attention
    work (per-token causal bounds skip them in-kernel). Admission
    consults the REFCOUNTED PREFIX CACHE first: a prompt matching a
    registered chain shares those KV pages (`PagedKVCache.
    acquire_prefix`, copy-on-write on divergence) and only prefills
    the rest — N users behind one system prompt pay for its KV once,
    and the page reservation is credited accordingly.

    With `ragged=False` the legacy loop alternates two phases: (1)
    ADMIT — while a slot and enough free pages for the worst case
    (prompt + max_new_tokens; conservative reservation = no mid-decode
    preemption) exist, prefill the next queued prompt whole and stream
    its first token; (2) DECODE — one fixed-shape jitted step advances
    every active sequence by one token (batch padded to a power-of-two
    bucket with rows targeting the reserved pad page — pad rows pay
    FULL attention work, which is what the ragged path eliminates).

    Either way sequences free their pages on finish without stalling
    neighbors. Decoding defaults to greedy (temperature 0 — an
    on-device argmax, deterministic and token-for-token equal to a
    single-sequence paged decode of the same prompt); on the ragged
    path `submit(..., sampling=SamplingParams(temperature=, top_k=,
    top_p=, seed=))` switches a request to REAL seeded sampling,
    computed inside the same fixed-shape jitted step (per-row config
    arrays — admit/evict never changes the compiled signature, and
    only int32 tokens ever cross to the host). The legacy bucketed
    path stays greedy-only.

    Disaggregation (the front door, docs/SERVING.md): `set_handoff(fn)`
    makes this engine the PREFILL role — a prompt whose last chunk
    just produced its first token is exported as a `KVChainHandle`
    (page ids, zero copies) and `fn(seq, chain)` moves it to a
    decode-role engine's `adopt()` over the SAME shared page pool.
    Admission reservations live pool-wide in the cache's claims
    ledger, so two engines admitting against one pool never
    double-book a page."""

    def __init__(self, model, n_pages=256, page_size=16, max_batch=8,
                 max_queue=64, max_new_tokens=64, eos_token_id=None,
                 cache=None, name=None, ragged=None, prefill_chunk=32,
                 prefix_cache=True, kv_snapshot_every=8,
                 speculative=None, draft_cache=None):
        self.name = name or f"gen{next(_ENGINE_IDS)}"
        for need in ("paged_decode_step", "make_paged_cache"):
            if not hasattr(model, need):
                raise TypeError(
                    f"GenerationEngine needs a model with {need}() "
                    "(e.g. models.gpt.GPTForCausalLM)")
        self.model = model
        self.cache = cache if cache is not None else \
            model.make_paged_cache(n_pages, page_size)
        # "paged" | "recurrent" | "hybrid" — stamped on every serve /
        # request / kvcache / journey record this engine emits, and the
        # schema's strategy-conditional rules key on it
        self.cache_strategy = strategy_of(self.cache)
        self.max_batch = int(max_batch)
        self.max_queue = int(max_queue)
        self.default_max_new = int(max_new_tokens)
        self.eos_token_id = eos_token_id
        self.ragged = bool(hasattr(model, "paged_ragged_step")
                           if ragged is None else ragged)
        if self.ragged and not hasattr(model, "paged_ragged_step"):
            raise TypeError("ragged=True needs model.paged_ragged_step()")
        self.prefill_chunk = max(1, int(prefill_chunk))
        self.prefix_cache = bool(prefix_cache) and self.ragged
        # speculative decoding (inference/speculative.py): a draft
        # model + its own page pool. `draft_cache` lets a disaggregated
        # pair SHARE one draft pool (the mid-speculation handoff rider
        # moves draft page ids, which cannot cross pools).
        self.speculative = speculative
        self._draft_cache = None
        self._spec_proposed = 0  # draft tokens proposed (this engine)
        self._spec_accepted = 0  # draft tokens accepted (this engine)
        if speculative is not None:
            from .speculative import SpeculativeConfig
            if not isinstance(speculative, SpeculativeConfig):
                raise TypeError(
                    "speculative must be a SpeculativeConfig, got "
                    f"{type(speculative).__name__}")
            if not self.ragged:
                raise ValueError(
                    "speculative decoding needs the ragged engine "
                    "path — the verify row rides the mixed "
                    "prefill/decode step")
            if self.cache_strategy != "paged":
                # rejecting a mispredicted draft run rewinds the kv
                # length cursor; a recurrent state blob has no past to
                # rewind to (cache.rollback raises for the same reason)
                raise ValueError(
                    "speculative decoding requires the paged cache "
                    f"strategy (engine cache is {self.cache_strategy!r})"
                    " — recurrent decode state is not rewindable")
            if not hasattr(speculative.draft_model, "paged_ragged_step"):
                raise TypeError(
                    "SpeculativeConfig.draft_model needs "
                    "paged_ragged_step() (e.g. GPTForCausalLM)")
            self._draft_cache = draft_cache if draft_cache is not None \
                else speculative.draft_model.make_paged_cache(
                    speculative.draft_pages or n_pages,
                    speculative.draft_page_size or page_size)
        # attention-slot accounting: how many kv score slots each step
        # COMPUTES vs how many were USEFUL (inside some row's causal
        # bound). The bucketed path computes pad_rows x full table
        # width; the ragged kernel computes only each token's own
        # ceil(bound/page) blocks — pad_token_fraction() is the
        # measured difference, not an estimate
        self._attn_computed = 0
        self._attn_useful = 0
        self.retraces = 0  # decode executables compiled in THIS engine
        self._synced_traces = self._model_traces()
        self._pending = deque()
        self._active = []        # list of _ActiveSeq, decode-batch order
        self._prefilling = []    # admitted, prompt KV still chunking in
        self._admitting = 0      # popped from pending, prefill in flight
        self._handoff_fn = None  # set_handoff: this engine = prefill role
        self._adopted = deque()  # chains handed to this engine (decode
        # role), adopted into _active by the scheduler thread
        self._step_prefix_hits = 0  # prefix tokens since last record
        self._cv = threading.Condition()
        self._stopping = False
        self._abort = False      # no-wait shutdown: fail active too
        self._next_sid = 0
        # pool observatory cadence: one kind:"kvcache" snapshot per
        # kv_snapshot_every steps (the first step always snapshots)
        self.kv_snapshot_every = max(1, int(kv_snapshot_every))
        self._step_i = 0
        self._kv_peak_held = 0   # peak pages held at any step
        _obs.register_engine(self)
        # memory-observatory attribution: the pool arrays live for the
        # engine's lifetime — register by strategy-stable tags (a
        # disaggregated pair sharing one pool registers it under two
        # engine tags; mem_report() dedups by buffer identity)
        if self.cache_strategy == "hybrid":
            _mobs.register(f"kv_pool.{self.name}", self.cache.paged)
            _mobs.register("ssm_state", self.cache.recurrent)
        elif self.cache_strategy == "recurrent":
            _mobs.register("ssm_state", self.cache)
        else:
            _mobs.register(f"kv_pool.{self.name}", self.cache)
        if self._draft_cache is not None:
            _mobs.register("draft_pool", self._draft_cache)
        self._thread = threading.Thread(
            target=_run_scheduler, args=(weakref.ref(self),),
            name="serve-decode", daemon=True)
        self._thread.start()

    # -- admission -------------------------------------------------------
    def submit(self, prompt_ids, max_new_tokens=None, eos_token_id=None,
               deadline_ms=None, sampling=None, slo_class=None,
               router=None):
        """Queue one prompt (1-D int array) for generation; returns a
        GenerationHandle. Rejects immediately (QueueFullError) when the
        queue is full, and validates the context limit up front. A
        `deadline_ms` that passes while the request is still QUEUED
        fails the handle with DeadlineExceeded (outcome "expired") —
        in-flight generation is never killed by its deadline, but the
        request record states whether it was met (`deadline_met`), and
        the SLO aggregates count it.

        `slo_class` / `router` carry the ServingRouter's identity
        stamps: they (and `handle.request_id`) land on the handle and
        trace HERE, before the enqueue makes the request visible to
        the scheduler thread — a fast prefill may stream, export, even
        finish the instant it is queued, and its records must already
        carry the id/class (a post-submit stamp would race).

        `sampling` (SamplingParams) picks this request's decode
        strategy: the default is greedy (temperature 0, bit-exact with
        the pre-sampling argmax path); temperature > 0 enables seeded
        on-device temperature/top-k/top-p sampling — ragged path only
        (the legacy bucketed decode stays greedy)."""
        prompt = np.asarray(
            prompt_ids.value if isinstance(prompt_ids, Tensor)
            else prompt_ids).astype(np.int64).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt must have at least one token")
        sp = GREEDY if sampling is None else sampling
        if not isinstance(sp, SamplingParams):
            raise TypeError(
                f"sampling must be a SamplingParams, got "
                f"{type(sp).__name__}")
        if not sp.greedy and not self.ragged:
            raise ValueError(
                "sampling (temperature > 0) needs the ragged engine "
                "path — the legacy bucketed decode is greedy-only")
        max_new = int(max_new_tokens) if max_new_tokens is not None \
            else self.default_max_new
        if max_new < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new}")
        limit = getattr(getattr(self.model, "cfg", None),
                        "max_position_embeddings", None)
        if limit is not None and prompt.size + max_new > limit:
            raise ValueError(
                f"prompt {prompt.size} + max_new_tokens {max_new} "
                f"exceeds max_position_embeddings {limit}")
        usable = self.cache.n_pages - 1  # page 0 is the reserved pad page
        if self.cache.pages_needed(prompt.size + max_new) > usable:
            raise ValueError(
                f"request needs {self.cache.pages_needed(prompt.size + max_new)} "
                f"pages (prompt {prompt.size} + max_new {max_new}) but the "
                f"cache only has {usable} usable — it could NEVER be "
                "admitted; grow n_pages or shorten the request")
        if self._draft_cache is not None:
            # the draft twin must ALSO always fit: its worst-case KV is
            # prompt + max_new + k tokens (the admission claim), and
            # its own context limit bounds the catch-up cursor
            dlimit = getattr(
                getattr(self.speculative.draft_model, "cfg", None),
                "max_position_embeddings", None)
            if dlimit is not None and prompt.size + max_new > dlimit:
                raise ValueError(
                    f"prompt {prompt.size} + max_new_tokens {max_new} "
                    f"exceeds the DRAFT model's "
                    f"max_position_embeddings {dlimit}")
            dneed = self._draft_cache.pages_needed(
                prompt.size + max_new + self.speculative.k)
            dusable = self._draft_cache.n_pages - 1
            if dneed > dusable:
                raise ValueError(
                    f"request needs {dneed} DRAFT pages (prompt "
                    f"{prompt.size} + max_new {max_new} + k "
                    f"{self.speculative.k}) but the draft cache only "
                    f"has {dusable} usable — it could NEVER be "
                    "admitted; grow draft_pages or shorten the request")
        eos = self.eos_token_id if eos_token_id is None else eos_token_id
        handle = GenerationHandle(prompt, max_new, eos)
        handle.sampling = sp
        # key data is host bit math; seed=None draws a process-unique
        # deterministic seed so an unseeded request still reproduces
        # within one process run
        handle.key = sp.key_data(fallback_seed=0) if sp.greedy \
            else sp.key_data(fallback_seed=next(_SEED_IDS))
        if deadline_ms is not None:
            handle.deadline = time.perf_counter() \
                + float(deadline_ms) / 1000.0
            handle.deadline_ms = float(deadline_ms)
        handle.trace = _obs.start_request(
            self.name, prompt_tokens=int(prompt.size),
            max_new_tokens=max_new,
            deadline_s=None if deadline_ms is None
            else float(deadline_ms) / 1000.0)
        handle.request_id = handle.trace.request_id
        handle.trace.cache_strategy = self.cache_strategy
        if slo_class is not None:
            handle.trace.slo_class = str(slo_class)
        if router is not None:
            handle.router = str(router)
        reject = None
        with self._cv:
            if self._stopping:
                reject = EngineStopped("engine is drained/shut down")
            elif len(self._pending) >= self.max_queue:
                _monitor.counter("serve.rejected").inc()
                reject = QueueFullError(
                    f"generation queue full ({self.max_queue} waiting)")
            else:
                self._pending.append(handle)
                _monitor.counter("serve.requests").inc()
                _monitor.gauge("serve.queue_depth").set(
                    len(self._pending))
                self._cv.notify_all()
        if reject is not None:
            # trace close OUTSIDE the lock: finish() appends to the
            # metrics JSONL, and file I/O must never stall the engine
            handle.trace.finish("rejected", error=str(reject))
            raise reject
        return handle

    # -- the scheduler/decode loop --------------------------------------
    def _model_traces(self):
        """The model's trace-time compile counters (legacy decode +
        ragged step), folded into serve.retraces by _sync_retraces.
        The DRAFT model's counter is included: a steady-state draft
        compile is just as much a retrace-contract violation as a
        target one."""
        n = getattr(self.model, "_paged_decode_traces", 0) \
            + getattr(self.model, "_ragged_traces", 0)
        if self.speculative is not None:
            n += getattr(self.speculative.draft_model,
                         "_ragged_traces", 0)
        return n

    def _loop_once(self):
        """One admit+step iteration (False = thread exits). The
        runner (_run_scheduler) re-calls while we return True, holding
        no strong engine ref in between."""
        with self._cv:
            if not self._pending and not self._active \
                    and not self._prefilling and not self._adopted:
                if self._stopping:
                    return False
                with _stat.span("serve.idle"):
                    self._cv.wait(0.05)  # idle: wait for work
                if not self._pending and not self._active \
                        and not self._prefilling and not self._adopted:
                    return True  # still idle: let the runner drop its ref
        if self._abort:
            # shutdown(wait=False): a long in-flight generation must
            # not keep this thread decoding past the join — fail the
            # active set (loop thread owns the cache) and exit
            self._fail_all(EngineStopped("engine shut down"))
            return False
        try:
            if self.ragged:
                # one scheduler turn is one `serve.step` span on this
                # thread; its children (serve.step.admit here, the rest
                # in _ragged_step) cover it
                with _stat.span("serve.step"):
                    with _stat.span("serve.step.admit"):
                        self._drain_adopted()
                        self._admit_ragged()
                    stepped = bool(self._active or self._prefilling)
                    if stepped:
                        self._ragged_step()
                if not stepped:
                    with self._cv:
                        if self._pending and not self._stopping:
                            with _stat.span("serve.idle"):
                                self._cv.wait(0.01)
                return True
            self._admit()
            if self._active:
                self._decode_step()
            else:
                # pending work that could not admit yet (pages held
                # by nothing — transient) must not busy-spin the
                # scheduler; submissions/evictions notify
                with self._cv:
                    if self._pending and not self._stopping:
                        self._cv.wait(0.01)
        except Exception as e:
            _monitor.counter("serve.errors").inc()
            self._fail_all(e)
        return True

    def _pop_doomed_head(self):
        """Queue-head triage shared by both admission loops. Caller
        HOLDS self._cv. A head that was cancelled while queued, or
        whose deadline passed, is popped — before paying any prefill
        or reserving pages — and returned as (outcome, handle) for
        `_close_doomed` to resolve OUTSIDE the lock (set_exception
        fires done-callbacks synchronously, and the trace close does
        file I/O). `_admitting` counts the handoff so drain() never
        observes "queue empty, nothing in flight" while the rejection
        is still pending. Returns None when the head is live."""
        handle = self._pending[0]
        outcome = None
        if handle.future.cancelled():
            outcome = "cancelled"
        elif handle.deadline is not None \
                and time.perf_counter() > handle.deadline:
            outcome = "expired"
            _monitor.counter("serve.expired").inc()
        if outcome is None:
            return None
        self._pending.popleft()
        _monitor.gauge("serve.queue_depth").set(len(self._pending))
        self._admitting += 1
        return outcome, handle

    def _close_doomed(self, doomed):
        """Resolve a popped dead head (scheduler thread, OUTSIDE the
        lock): reject expiries, close the trace and the stream, then
        release the drain() handoff."""
        outcome, handle = doomed
        try:
            if outcome == "expired":
                _reject_future(handle.future, DeadlineExceeded(
                    "deadline passed before admission"))
            if handle.trace is not None:
                handle.trace.finish(outcome)
            handle._close()
        finally:
            with self._cv:
                self._admitting -= 1
                self._cv.notify_all()

    def _admit(self):
        """Prefill queued prompts into free slots between decode steps.
        Admission reserves the worst case (prompt + max_new tokens of
        pages) so a decoding sequence can never hit out-of-pages."""
        while True:
            doomed = None
            with self._cv:
                if not self._pending:
                    return
                # triage BEFORE the capacity gate: a saturated engine
                # must still shed expired/cancelled heads — overload is
                # exactly the regime deadline shedding exists for
                doomed = self._pop_doomed_head()
                if doomed is None:
                    if len(self._active) >= self.max_batch:
                        return
                    handle = self._pending[0]
                    # the cache lock spans the capacity check AND the
                    # claim registration: a second engine sharing this
                    # pool cannot admit into the same free pages
                    # between the two (claims are POOL-wide — see
                    # PagedKVCache.outstanding_claims)
                    with self.cache.lock:
                        need = self.cache.pages_needed(
                            handle.prompt.size + handle.max_new_tokens)
                        # allocation is LAZY: live sequences still hold
                        # claims on pages they haven't drawn yet —
                        # admit only against what's free AFTER every
                        # outstanding reservation on this pool
                        outstanding = self.cache.outstanding_claims()
                        if not self.cache.can_allocate(
                                handle.prompt.size
                                + handle.max_new_tokens,
                                reserved=outstanding):
                            return  # wait for evictions to free pages
                        sid = self._new_sid()
                        self.cache.add_sequence(sid)
                        self.cache.set_claim(sid, need)
                    self._pending.popleft()
                    self._admitting += 1  # drain() must see the handoff
                    _monitor.gauge("serve.queue_depth").set(
                        len(self._pending))
                    if handle.trace is not None:
                        handle.trace.admitted()
            if doomed is not None:
                self._close_doomed(doomed)
                continue
            try:
                seq = _ActiveSeq(sid, handle, need)
                try:
                    logits = self.model.paged_decode_step(
                        self.cache, [sid],
                        Tensor(jnp.asarray(handle.prompt[None, :])))
                    # sampling ON DEVICE: the argmax runs in XLA and
                    # one int32 crosses to the host via an async copy —
                    # the decode loop never blocks on a [vocab]-sized
                    # D2H (the old np.asarray(...).argmax() hot-sync);
                    # int() collects the already-in-flight copy
                    tok_dev = jnp.argmax(logits.value[0])
                    tok_dev.copy_to_host_async()
                    tok = int(tok_dev)
                except Exception as e:
                    with self.cache.lock:
                        self.cache.free_sequence(sid)
                    _reject_future(handle.future, e)
                    _finish_trace(handle.trace, e)
                    handle._close()
                    continue
                _monitor.histogram("serve.ttft_s").observe(
                    time.perf_counter() - handle.t_submit)
                self._sync_retraces()
                self._active.append(seq)  # lint-ok[unlocked-shared-state]: scheduler-thread-owned list; other threads only take GIL-atomic list()/len() snapshots (load_report, _note_kv_step extras)
                self._emit(seq, tok)
            finally:
                with self._cv:
                    self._admitting -= 1
                    self._cv.notify_all()

    def _new_sid(self):
        """Engine-unique sequence id. Prefixed with the engine name:
        several engines sharing one page pool (prefill/decode
        disaggregation) must never collide on a sid."""
        sid = f"{self.name}.g{self._next_sid}"
        self._next_sid += 1
        return sid

    # -- speculative decoding plumbing (inference/speculative.py) -------
    def _free_draft(self, seq):
        """Free a sequence's DRAFT-cache twin (every target free site
        calls this — a leaked draft claim would starve two-pool
        admission). Idempotent: clears seq.draft_sid."""
        dsid = seq.draft_sid
        if dsid is None or self._draft_cache is None:
            return
        seq.draft_sid = None
        try:
            with self._draft_cache.lock:
                self._draft_cache.free_sequence(dsid)
        except KeyError:
            pass  # already freed (e.g. _fail_all racing a free site)

    def _free_draft_sid(self, dsid):
        """_free_draft for detached (handle, sid) tuples that no longer
        carry the _ActiveSeq."""
        if dsid is None or self._draft_cache is None:
            return
        try:
            with self._draft_cache.lock:
                self._draft_cache.free_sequence(dsid)
        except KeyError:
            pass

    def _release_chain_pair(self, chain):
        """Release a handed-off chain AND its draft rider back to their
        pools (cancelled adoptions, dispatcher failures, shutdown).
        Lock order target-cache -> draft-cache, taken sequentially."""
        try:
            with self.cache.lock:
                self.cache.release_chain(chain)
        except Exception:
            pass
        dchain = getattr(chain, "draft_chain", None)
        if dchain is not None and self._draft_cache is not None:
            try:
                with self._draft_cache.lock:
                    self._draft_cache.release_chain(dchain)
            except Exception:
                pass

    # -- prefill/decode disaggregation (the front door) ------------------
    def set_handoff(self, fn):
        """Wire this engine as the PREFILL role of a disaggregated
        pair: when a prompt's last chunk produces its first token, the
        sequence's KV chain is exported (`PagedKVCache.export_chain` —
        page ids move, nothing copies) and `fn(seq, chain)` is called
        on the scheduler thread to place it on a decode-role engine
        (normally `ServingRouter`'s handoff dispatcher calling
        `decode_engine.adopt`). fn raising fails the request onto its
        handle and releases the chain. Pass None to unwire."""
        if fn is not None and not self.ragged:
            raise ValueError(
                "prefill-role handoff needs the ragged engine path")
        self._handoff_fn = fn  # lint-ok[unlocked-shared-state]: one-shot wiring at router construction, before any traffic; a function-reference store is GIL-atomic and the loop thread only reads it

    def adopt(self, handle, chain, last_token, generated, cached=0):
        """DECODE-role entry (any thread): accept a chain prefilled by
        another engine over the SAME shared page pool. The scheduler
        thread attaches it under a fresh sid (`adopt_chain` — page
        identity, refcounts, and the admission claim all carry over)
        and the sequence joins the decode batch at its next step,
        continuing token-for-token as if it had prefetched here."""
        if not self.ragged:
            # symmetric with set_handoff's prefill-side guard: only the
            # ragged scheduler drains _adopted — accepting the chain
            # here would park it (and its pages + claim) forever
            raise ValueError(
                "decode-role adoption needs the ragged engine path")
        # split the request trace at the handoff boundary: the prefill
        # trace closes with outcome "handoff", a fresh decode-side
        # trace (SAME request_id, original t_submit — deadline math
        # spans the whole request) rides the handle from here, and a
        # fleet_observatory Journey joins the pair at decode-terminal
        # time. Built BEFORE the enqueue (pure host arithmetic): once
        # the entry is in _adopted the scheduler thread may finish the
        # request at any moment, and it must finish the DECODE trace.
        old_trace, new_trace, journey = handle.trace, None, None
        if old_trace is not None:
            from ..profiler import fleet_observatory as _fobs
            new_trace = _obs.start_request(
                self.name, prompt_tokens=old_trace.prompt_tokens,
                max_new_tokens=old_trace.max_new_tokens,
                deadline_s=old_trace.deadline_s)
            new_trace.request_id = old_trace.request_id
            new_trace.t_submit = old_trace.t_submit
            new_trace.slo_class = old_trace.slo_class
            new_trace.cache_strategy = self.cache_strategy
            new_trace.prefix_hit_tokens = old_trace.prefix_hit_tokens
            new_trace.generated_tokens = len(generated)
            # speculation counts survive the handoff split: the decode
            # trace keeps accumulating where the prefill trace stopped,
            # so journey reconciliation sees one request's totals
            new_trace.proposed_tokens = old_trace.proposed_tokens
            new_trace.accepted_tokens = old_trace.accepted_tokens
            new_trace.handoff_of = old_trace.engine
            old_trace.handoff_of = self.name
            journey = _fobs.Journey(
                handle=handle, prefill_trace=old_trace,
                decode_engine=self.name, chain=chain,
                page_size=int(self.cache.page_size))
            new_trace.journey = journey
        with self._cv:
            if self._stopping:
                raise EngineStopped(
                    "decode engine is drained/shut down")
            if new_trace is not None:
                handle.trace = new_trace
            self._adopted.append(
                (handle, chain, int(last_token), list(generated),
                 int(cached)))
            self._cv.notify_all()
        # close the prefill half OUTSIDE _cv: finish() appends to the
        # metrics JSONL, and file I/O must never run under the decode
        # scheduler's condition lock
        if old_trace is not None:
            old_trace.finish("handoff")

    def _drain_adopted(self):
        """Move handed-off chains into the active decode set
        (scheduler thread, called before admission each iteration).
        Respects max_batch — an over-capacity chain waits in the
        adoption queue, its pages and claim safely parked in the
        chain handle."""
        while True:
            with self._cv:
                if not self._adopted:
                    return
                if len(self._active) + len(self._prefilling) \
                        >= self.max_batch:
                    return
                handle, chain, last, generated, cached = \
                    self._adopted.popleft()
            if handle.future.cancelled():
                self._release_chain_pair(chain)
                if handle.trace is not None:
                    handle.trace.finish("cancelled")
                handle._close()
                continue
            sid = self._new_sid()
            with self.cache.lock:
                self.cache.adopt_chain(sid, chain)
            # speculative rider: adopt the draft chain alongside the
            # target one (same draft pool — a disaggregated pair shares
            # it via the draft_cache= constructor arg). A rider from a
            # FOREIGN pool cannot adopt (page ids don't cross pools):
            # release it and rebuild draft state below. A chain with no
            # rider (prefill engine ran non-speculatively) gets a fresh
            # draft twin when the pool has room, or decodes
            # non-speculatively — adoption must never block on the
            # draft pool.
            draft_sid, dlen = None, 0
            dchain = getattr(chain, "draft_chain", None)
            if self._draft_cache is not None:
                dc = self._draft_cache
                if dchain is not None:
                    try:
                        with dc.lock:
                            dc.adopt_chain(f"{sid}.d", dchain)
                        draft_sid, dlen = f"{sid}.d", int(dchain.length)
                    except ValueError:
                        with dc.lock:
                            dc.release_chain(dchain)
                        dchain = None
                if draft_sid is None:
                    dneed = dc.pages_needed(
                        handle.prompt.size + handle.max_new_tokens
                        + self.speculative.k)
                    with dc.lock:
                        if dneed + dc.outstanding_claims() <= \
                                dc.n_free_pages() \
                                + dc.n_evictable_pages():
                            draft_sid = f"{sid}.d"
                            dc.add_sequence(draft_sid)
                            dc.set_claim(draft_sid, dneed)
            trace = handle.trace
            if trace is not None:
                trace.admitted()  # decode-side admission boundary
                if trace.journey is not None:
                    # the MEASURED end of the handoff gap: the chain
                    # is attached and the sequence joins the decode
                    # batch at the next step
                    trace.journey.adopted()
            seq = _ActiveSeq(sid, handle, chain.claim, cached=cached)
            seq.generated = list(generated)
            seq.last = last
            seq.filled = int(handle.prompt.size)
            seq.draft_sid = draft_sid
            seq.dlen = dlen
            self._active.append(seq)  # lint-ok[unlocked-shared-state]: scheduler-thread-owned list (adoption), same contract as the admission append

    def _handoff_seq(self, seq, tok):
        """PREFILL role epilogue (scheduler thread): the prompt's last
        chunk just produced the first sampled token. Stream it, then
        hand the chain to the decode engine instead of joining the
        local decode batch — unless the request is already terminal
        (cancelled, eos on the first token, max_new_tokens == 1),
        which finishes here exactly like the single-engine path."""
        h = seq.handle
        if h.future.cancelled():
            with self.cache.lock:
                self.cache.free_sequence(seq.sid)
            self._free_draft(seq)
            if h.trace is not None:
                h.trace.finish("cancelled")
            h._close()
            with self._cv:
                self._cv.notify_all()
            return
        if h.trace is not None:
            h.trace.first_token()
            h.trace.note_token(self.cache.pages_held(seq.sid))
        _monitor.counter("serve.generated_tokens").inc()
        seq.generated.append(tok)
        seq.last = tok
        h._push(tok)
        if (h.eos_token_id is not None and tok == h.eos_token_id) \
                or len(seq.generated) >= h.max_new_tokens:
            with self.cache.lock:
                if self.prefix_cache and seq.filled >= h.prompt.size:
                    self.cache.register_prefix(seq.sid, h.prompt)
                self.cache.free_sequence(seq.sid)
            self._free_draft(seq)
            _monitor.histogram("serve.latency_s").observe(
                time.perf_counter() - h.t_submit)
            if h.trace is not None:
                h.trace.finish("completed")
            final = np.asarray(seq.generated, np.int64)  # hot-sync-ok: host int list, not a device read
            _resolve_future(h.future, final)
            h._close()
        else:
            with self.cache.lock:
                chain = self.cache.export_chain(seq.sid)
            # journey riders, stamped AT the export site: the id that
            # joins route + both request records, and the measured
            # start of the handoff gap (the chain is not shared with
            # the decode engine until _handoff_fn below)
            chain.request_id = getattr(h.trace, "request_id", None) \
                or h.request_id
            chain.t_export = time.perf_counter()
            # speculative rider: export the draft twin alongside — the
            # decode role adopts both in one unit (a mid-speculation
            # chain keeps its catch-up cursor, no re-prefill)
            if seq.draft_sid is not None and \
                    self._draft_cache is not None:
                with self._draft_cache.lock:
                    chain.draft_chain = \
                        self._draft_cache.export_chain(seq.draft_sid)
                seq.draft_sid = None
            try:
                # NOT holding any lock: the dispatcher enqueues on the
                # decode engine (its _cv) and emits the route record
                self._handoff_fn(seq, chain)
            except Exception as e:
                self._release_chain_pair(chain)
                _reject_future(h.future, e)
                _finish_trace(h.trace, e)
                h._close()
        with self._cv:
            self._cv.notify_all()  # slot freed / pages handed off

    def _decode_step(self):
        """ONE jitted step for every active sequence: the decode batch
        is padded to a power-of-two bucket (rows that scatter into the
        reserved pad page), so the compiled program's shapes are fixed
        while sequences join and leave."""
        sids = [s.sid for s in self._active]
        toks = np.asarray([[s.last] for s in self._active], np.int64)  # hot-sync-ok: host int list, not a device read
        b = len(sids)
        lens = [self.cache.length(s) for s in sids]  # pre-advance
        pad_to = min(1 << (b - 1).bit_length(),
                     1 << (self.max_batch - 1).bit_length())
        pad_to = max(pad_to, b)
        logits = self.model.paged_decode_step(
            self.cache, sids, Tensor(jnp.asarray(toks)), pad_to=pad_to)
        # argmax ON DEVICE, async copy launched at dispatch: the step's
        # one deliberate sync below reads B int32s, never [B, vocab]
        nxt_dev = jnp.argmax(logits.value, axis=-1)
        nxt_dev.copy_to_host_async()
        nxt = np.asarray(nxt_dev)  # hot-sync-ok: sampling sync point — B int32s, argmax already ran on device
        self._sync_retraces()
        now = time.perf_counter()
        # slot-accurate pad accounting: the fixed-shape kernel computes
        # pad_to rows x the POW2-BUCKETED table width x page_size
        # score slots, of which only each real row's (len+1) lie inside
        # a causal bound — shorter rows pay for the longest row's table
        # and pad rows pay for everything (the waste the ragged kernel
        # skips per-token)
        width = self._pow2(max(self.cache.pages_held(s) for s in sids))
        computed = int(pad_to) * width * self.cache.page_size
        useful = sum(l + 1 for l in lens)
        self._attn_computed += computed  # lint-ok[unlocked-shared-state]: loop-thread-owned monotonic counter; pad_token_fraction's lock-free read tolerates a one-step-stale ratio
        self._attn_useful += useful  # lint-ok[unlocked-shared-state]: paired with _attn_computed above — same single-writer telemetry counter
        _monitor.histogram("serve.batch_size").observe(b)
        _monitor.counter("serve.pad_tokens").inc(int(pad_to - b))
        _monitor.export_step(
            {"engine": self.name, "requests": b, "batch_size": b,
             "bucket_batch": int(pad_to),
             "cache_strategy": self.cache_strategy,
             "queue_depth": len(self._pending),  # lint-ok[unlocked-shared-state]: GIL-atomic len() in the loop thread's telemetry export; worst case one submit of staleness
             "pad_tokens": int(pad_to - b),
             "pad_token_fraction": max(0.0, 1.0 - useful / computed),
             "prefix_hits": 0, "shared_pages": 0,
             "chunked_prefill_tokens": 0,
             "proposed_tokens": 0, "accepted_tokens": 0,
             "accept_rate": 0.0,  # bucketed path never speculates
             # for decode batches latency_s is the mean IN-FLIGHT age of
             # the step's requests (they are not finished yet)
             "latency_s": sum(now - s.handle.t_submit
                              for s in self._active) / b}, kind="serve")
        for seq, tok in zip(list(self._active), nxt):
            self._emit(seq, int(tok))
        self._note_kv_step()

    def pad_token_fraction(self):
        """Measured fraction of this engine's attention score slots
        spent OUTSIDE any row's causal bound — pad rows, bucketed
        table width, intra-page remainders. The bucketed decode path
        pays all three; the ragged kernel pays only the last."""
        if not self._attn_computed:
            return 0.0
        return max(0.0, 1.0 - self._attn_useful / self._attn_computed)

    # -- the ragged loop: chunked prefill + prefix caching --------------
    @staticmethod
    def _pow2(n):
        return 1 << (max(int(n), 1) - 1).bit_length()

    def _admit_ragged(self):
        """Move queued prompts into the prefilling set — NO compute
        here, the mixed step does the prefill in chunks. Admission
        reserves the worst case (prompt + max_new pages) CREDITED with
        the prefix cache's fully-matched pages, against the free list
        plus the registry's evictable retention."""
        while True:
            doomed = None
            with self._cv:
                if not self._pending:
                    return
                # triage BEFORE the capacity gate (see _admit): shed
                # expired/cancelled heads even at max_batch
                doomed = self._pop_doomed_head()
                if doomed is None:
                    in_flight = len(self._active) + len(self._prefilling)
                    if in_flight >= self.max_batch:
                        return
                    handle = self._pending[0]
                    # ONE cache-locked section from the prefix match to
                    # the claim: with a second engine sharing this pool
                    # (disaggregation) nothing may slip between the
                    # capacity check and the reservation it justifies
                    with self.cache.lock:
                        matched_full = pinned = 0
                        if self.prefix_cache:
                            # at most prompt-1 cached tokens: the final
                            # prompt token must run through the model
                            # to produce the first sampled token's
                            # logits
                            _, matched_full, pinned = \
                                self.cache.match_prefix_credit(
                                    handle.prompt,
                                    max_tokens=handle.prompt.size - 1)
                        need = self.cache.pages_needed(
                            handle.prompt.size + handle.max_new_tokens) \
                            - matched_full
                        # claims compare against pages DRAWN, not held:
                        # an acquired shared prefix inflates pages_held
                        # without consuming the pool, and its
                        # copy-on-write + tail pages are still owed.
                        # outstanding_claims is POOL-wide — every
                        # engine's reservations count, plus chains in
                        # handoff limbo
                        outstanding = self.cache.outstanding_claims()
                        # supply subtracts `pinned`: matched
                        # registry-only pages count as evictable TODAY
                        # but acquire_prefix pins them — crediting need
                        # AND counting them as supply would admit
                        # against phantom capacity
                        if need + outstanding > self.cache.n_free_pages() \
                                + self.cache.n_evictable_pages() - pinned:
                            return  # wait for evictions to free pages
                        sid = self._new_sid()
                        self.cache.add_sequence(sid)
                        cached = 0
                        if self.prefix_cache:
                            cached = self.cache.acquire_prefix(
                                sid, handle.prompt,
                                max_tokens=handle.prompt.size - 1)
                        self.cache.set_claim(sid, need)
                        # TWO-POOL admission (speculative decoding):
                        # the draft model's cache is a second claims
                        # ledger — gate + claim it here, still under
                        # the TARGET pool's lock (lock order
                        # target-cache -> draft-cache everywhere), so
                        # two engines over the shared pools can never
                        # interleave between the gates. A full draft
                        # pool unwinds the target claim and waits —
                        # admission must never half-book a request.
                        draft_sid = None
                        if self._draft_cache is not None:
                            dc = self._draft_cache
                            dneed = dc.pages_needed(
                                handle.prompt.size
                                + handle.max_new_tokens
                                + self.speculative.k)
                            with dc.lock:
                                if dneed + dc.outstanding_claims() > \
                                        dc.n_free_pages() \
                                        + dc.n_evictable_pages():
                                    self.cache.free_sequence(sid)
                                    return
                                draft_sid = f"{sid}.d"
                                dc.add_sequence(draft_sid)
                                dc.set_claim(draft_sid, dneed)
                    self._pending.popleft()
                    _monitor.gauge("serve.queue_depth").set(
                        len(self._pending))
                    if handle.trace is not None:
                        handle.trace.admitted()
                    if cached:
                        _monitor.counter("serve.prefix_hits").inc(cached)
                        self._step_prefix_hits += cached
                        if handle.trace is not None:
                            handle.trace.note_prefix(cached)
                    # appended UNDER self._cv: pop->prefilling is one
                    # atomic transition, so drain() never observes
                    # "queue empty, nothing in flight" mid-admission
                    seq = _ActiveSeq(sid, handle, need, cached=cached)
                    seq.draft_sid = draft_sid
                    self._prefilling.append(seq)
                    continue
            if doomed is not None:
                self._close_doomed(doomed)

    def _hist_slice(self, s, start, stop):
        """Token ids [start:stop) of a sequence's FULL history (prompt
        then generated) as host ints — the draft catch-up feed. Pure
        host indexing; neither array is copied whole."""
        p = s.handle.prompt
        ps = int(p.size)
        out = []
        if start < ps:
            out.extend(int(t) for t in p[start:min(stop, ps)])
        if stop > ps:
            out.extend(int(t)
                       for t in s.generated[max(start - ps, 0):stop - ps])
        return out

    def _spec_rows(self, rows, seqs):
        """One DRAFT-model ragged step (scheduler thread; same
        token/row bucketing rules as the target step so the draft's
        warm schedule covers it) returning each row's next-token
        sample as host ints. Rows draw with their request's own
        sampling config — `draft_temperature` overriding the
        temperature, the bench's accept-rate knob — keyed by the same
        fold_in(request_key, position) the target's acceptance draw
        uses; catch-up-only rows' samples are simply discarded."""
        spec = self.speculative
        from ..ops.pallas.attention_core import MIN_Q_TOKENS
        t_real = sum(len(t) for _, t in rows)
        b_real = len(rows)
        pad_t = max(self._pow2(t_real), MIN_Q_TOKENS)
        pad_b = min(self._pow2(b_real), self._pow2(self.max_batch))
        temps = np.zeros((pad_b,), np.float32)
        top_ks = np.zeros((pad_b,), np.int32)
        top_ps = np.ones((pad_b,), np.float32)
        keys = np.zeros((pad_b, 2), np.uint32)
        for i, s in enumerate(seqs):
            sp = s.sampling
            t_eff = 0.0 if sp is None else float(sp.temperature)  # hot-sync-ok: host float of a SamplingParams field, not a device read
            if spec.draft_temperature is not None:
                t_eff = spec.draft_temperature
            if t_eff > 0:
                temps[i] = t_eff
                top_ks[i] = (sp.top_k or 0) if sp is not None else 0
                top_ps[i] = 1.0 if sp is None or sp.top_p is None \
                    else sp.top_p
                keys[i] = s.key
        _, nxt = spec.draft_model.paged_ragged_step(
            self._draft_cache, rows, pad_to_tokens=pad_t,
            pad_to_rows=pad_b,
            sampling=(temps, top_ks, top_ps, keys))
        return [int(t) for t in jax.device_get(nxt)]  # hot-sync-ok: draft proposal sync — b_real int32s, each feeds the next draft step's input tokens

    def _spec_propose(self):
        """Draft-model proposal pass (scheduler thread), ONE iteration:

        phase 1 — one CATCH-UP row per draft-backed sequence feeds the
        draft the history tokens its cursor (seq.dlen) hasn't written
        KV for: prefix-cache-hit prompt tokens the target never
        computed, a whole adopted prompt after a rider-less handoff,
        the 2-token lag a fully-accepted (bonus) verify row leaves —
        capped at max(prefill_chunk, 2) tokens so a cold draft admits
        incrementally exactly like target prefill. A row that reaches
        the anchor token (seq.last) makes the sequence READY: its
        final sample IS the first proposal d_1.

        steps 2..k — each feeds the previous proposal back as a
        1-token row per ready sequence, producing d_j keyed at the
        same absolute position as the target's v_{j-1} draw.

        Returns {sid: [d_1..d_k_eff]} for the sequences whose next
        target row should be a VERIFY row (k_eff = min(k,
        remaining - 1); the last token of a request is never worth
        drafting). Sequences still catching up are absent — the
        target decodes them non-speculatively this iteration — and
        draft KV past the accepted prefix is rolled back by
        _ragged_step once the verdict is in."""
        spec = self.speculative
        cap = max(self.prefill_chunk, 2)
        plans, rows = [], []
        for s in list(self._active) + list(self._prefilling):
            if s.draft_sid is None:
                continue
            n_hist = int(s.handle.prompt.size) + len(s.generated)
            take = min(n_hist - s.dlen, cap)
            if take <= 0:
                continue  # prefilling twin fully caught up: no anchor yet
            remaining = s.handle.max_new_tokens - len(s.generated)
            k_eff = 0 if s.last is None else min(spec.k, remaining - 1)
            ready = s.dlen + take == n_hist and k_eff >= 1 \
                and s in self._active
            rows.append((s.draft_sid,
                         self._hist_slice(s, s.dlen, s.dlen + take)))
            plans.append((s, k_eff, ready, take))
        if not rows:
            return {}
        drafts, live = {}, []
        toks = self._spec_rows(rows, [p[0] for p in plans])
        for (s, k_eff, ready, take), tok in zip(plans, toks):
            s.dlen += take
            if ready:
                drafts[s.sid] = [tok]
                live.append((s, k_eff))
        for j in range(2, spec.k + 1):
            feed = [(s, k_eff) for s, k_eff in live if k_eff >= j]
            if not feed:
                break
            rows = [(s.draft_sid, [drafts[s.sid][-1]]) for s, _ in feed]
            toks = self._spec_rows(rows, [s for s, _ in feed])
            for (s, _), tok in zip(feed, toks):
                s.dlen += 1
                drafts[s.sid].append(tok)
        return drafts

    def _ragged_step(self):
        """ONE jitted mixed step over the Pallas ragged kernel: every
        active sequence's decode token — or, with speculative decoding
        on, its anchor + k-token draft proposal VERIFIED as one
        prefill-shaped row — plus up to `prefill_chunk` prompt tokens
        of the prefilling set, token/row counts padded to power-of-two
        buckets whose pad slots the kernel SKIPS (bound 0) — fixed
        compiled shapes with zero attention work on padding. Sampling
        is an on-device argmax (or the seeded per-position draw); the
        host reads back one int32 per row — per TOKEN when verifying
        drafts — through a copy launched at dispatch."""
        with _stat.span("serve.step.plan"):
            for s in list(self._prefilling):  # cancelled mid-prefill: evict
                if s.handle.future.cancelled():
                    with self.cache.lock:
                        self.cache.free_sequence(s.sid)
                    self._free_draft(s)
                    self._prefilling.remove(s)  # lint-ok[unlocked-shared-state]: scheduler-thread-owned list; readers take GIL-atomic list() snapshots, remove() is C-level atomic
                    if s.handle.trace is not None:
                        s.handle.trace.finish("cancelled")
                    s.handle._close()
            spec_on = self._draft_cache is not None
            drafts = self._spec_propose() if spec_on else {}
            rows, metas = [], []
            for s in self._active:
                d = drafts.get(s.sid)
                if d:
                    # verify row: the anchor token (whose KV the target
                    # hasn't written yet) + the draft's proposals, one
                    # prefill-shaped row — its k+1 <= MIN_Q_TOKENS tokens
                    # pad into the same bucket a 1-token decode row does
                    rows.append((s.sid, [s.last] + d))
                    metas.append(("verify", s, 1 + len(d)))
                else:
                    rows.append((s.sid, [s.last]))
                    metas.append(("decode", s, 1))
            budget = self.prefill_chunk
            # shortest-remaining-first: a short chat's 4 tokens must not
            # queue behind a long document's 15 chunks — the short one
            # finishes its prefill (and streams its first token) within a
            # step or two while the long one keeps absorbing the leftover
            # budget each step
            order = sorted(self._prefilling,
                           key=lambda s: s.handle.prompt.size - s.filled)
            for s in order:
                if budget <= 0:
                    break
                n = min(budget, s.handle.prompt.size - s.filled)
                rows.append((s.sid, s.handle.prompt[s.filled:s.filled + n]))
                metas.append(("prefill", s, n))
                if s.handle.trace is not None:
                    s.handle.trace.note_chunk()
                budget -= n
            if not rows:
                return
            t_real = sum(n for _, _, n in metas)
            b_real = len(rows)
            # the token bucket floors at MIN_Q_TOKENS so every q-block the
            # kernel forms reaches the MXU's 8-row sublane tile (a pure-
            # decode step of 1-3 rows would otherwise dispatch the old
            # [1, D] VPU-shaped dots); the extra slots carry bound 0 and
            # compute NOTHING — they ride sublanes the narrow dot wasted
            from ..ops.pallas.attention_core import MIN_Q_TOKENS
            pad_t = max(self._pow2(t_real), MIN_Q_TOKENS)
            pad_b = min(self._pow2(b_real), self._pow2(self.max_batch))
            # slot-accurate accounting (pre-dispatch: lengths advance in
            # the step): each token computes exactly ceil(bound/page)
            # pages of score slots — pad slots compute NOTHING (kernel
            # predicate), so the only waste is the intra-page remainder.
            # ragged_work_plan is the kernel's own work formula: the
            # metric and the in-kernel counter cannot diverge
            if self.cache_strategy == "recurrent":
                # no kv pages to walk: the scan kernel's time loop runs
                # pad_t constant-cost state updates, of which t_real are
                # real tokens — THAT is the strategy's pad overhead
                computed = int(pad_t)
                useful = int(t_real)
            else:
                from ..ops.pallas.paged_attention import ragged_work_plan
                P = self.cache.page_size
                bounds = np.concatenate(
                    [self.cache.length(sid) + np.arange(1, len(toks) + 1)
                     for sid, toks in rows])
                computed = int(ragged_work_plan(bounds, P).sum()) * P
                useful = int(bounds.sum())
            self._attn_computed += computed  # lint-ok[unlocked-shared-state]: loop-thread-owned monotonic counter (ragged site), same contract as the bucketed decode site
            self._attn_useful += useful  # lint-ok[unlocked-shared-state]: paired with _attn_computed above — same single-writer telemetry counter
            # per-row sampling config, [pad_b]-shaped like the row axis so
            # the compiled signature still keys on (T, B, W) only: pad and
            # greedy rows carry temperature 0 (the bit-exact argmax lane),
            # sampled rows their request's temperature/top-k/top-p and the
            # per-SEQUENCE base key (the step folds in the token position)
            temps = np.zeros((pad_b,), np.float32)
            top_ks = np.zeros((pad_b,), np.int32)
            top_ps = np.ones((pad_b,), np.float32)
            keys = np.zeros((pad_b, 2), np.uint32)
            for i, (_, s, _) in enumerate(metas):
                sp = s.sampling
                if sp is not None and not sp.greedy:
                    temps[i] = sp.temperature
                    top_ks[i] = sp.top_k or 0
                    top_ps[i] = 1.0 if sp.top_p is None else sp.top_p
                    keys[i] = s.key
        # the step's sizes ride on the event, as run and as padded; an
        # inline compile nests as jit.trace_lower / jit.compile
        with _stat.span("serve.step.dispatch", tokens=t_real, rows=b_real,
                        bucket_tokens=pad_t, bucket_rows=pad_b):
            try:
                if spec_on:
                    # same executable — the jitted step always computes the
                    # per-token sample lane; return_per_token only changes
                    # which Python-level outputs we keep
                    _, nxt, nxt_tok = self.model.paged_ragged_step(
                        self.cache, rows, pad_to_tokens=pad_t,
                        pad_to_rows=pad_b,
                        sampling=(temps, top_ks, top_ps, keys),
                        return_per_token=True)
                    nxt_tok.copy_to_host_async()  # overlap with bookkeeping
                else:
                    _, nxt = self.model.paged_ragged_step(
                        self.cache, rows, pad_to_tokens=pad_t,
                        pad_to_rows=pad_b,
                        sampling=(temps, top_ks, top_ps, keys))
                    nxt.copy_to_host_async()  # overlap with the bookkeeping
            except RuntimeError as e:
                if _mobs.is_oom(e):
                    # allocator exhaustion mid-decode: dump mem_state.json
                    # forensics (the kv pool is usually the top holder)
                    # before the scheduler's crash path sees it
                    raise _mobs.oom_error(e, site="serve.ragged_step") from e
                raise
        with _stat.span("serve.step.telemetry"):
            self._sync_retraces()
            now = time.perf_counter()
            prefill_toks = sum(n for k, _, n in metas if k == "prefill")
            _monitor.histogram("serve.batch_size").observe(b_real)
            if prefill_toks:
                _monitor.counter("serve.chunked_prefill_tokens").inc(
                    prefill_toks)
            shared = self.cache.shared_page_count()
            _monitor.gauge("serve.shared_pages").set(shared)
            hits, self._step_prefix_hits = self._step_prefix_hits, 0
            rec = {"engine": self.name, "requests": b_real,
                   "batch_size": b_real, "bucket_batch": int(pad_b),
                   "tokens": int(t_real), "bucket_tokens": int(pad_t),
                   "cache_strategy": self.cache_strategy,
                   "queue_depth": len(self._pending),
                   # pad SLOTS exist (pad_t - t_real) but carry bound 0: the
                   # kernel computes zero attention blocks for them, so the
                   # compute-bearing pad count — what serve.pad_tokens has
                   # always measured — is 0 by construction on this path,
                   # and the slot fraction is only the intra-page remainder
                   "pad_tokens": 0,
                   "pad_token_fraction": max(0.0, 1.0 - useful / computed)
                   if computed else 0.0,
                   "pad_slots": int(pad_t - t_real),
                   "prefix_hits": hits, "shared_pages": shared,
                   "chunked_prefill_tokens": prefill_toks,
                   "latency_s": sum(now - s.handle.t_submit
                                    for _, s, _ in metas) / b_real}
        with _stat.span("serve.step.fetch"):
            if spec_on:
                per_tok = jax.device_get(nxt_tok)  # hot-sync-ok: the step's one sync — t_real int32s (the per-token verify lane), copy launched at dispatch
            else:
                toks = jax.device_get(nxt)  # hot-sync-ok: the step's one sync — b_real int32s, copy launched at dispatch
        with _stat.span("serve.step.emit"):
            step_prop = step_acc = 0
            i = off = 0
            for kind, s, n in metas:
                row0 = off
                off += n
                tok = int(per_tok[row0 + n - 1]) if spec_on else int(toks[i])
                i += 1
                if kind == "verify":
                    d = drafts[s.sid]
                    samples = [int(per_tok[row0 + j]) for j in range(n)]
                    m = accept_length(d, samples)
                    k_eff = n - 1
                    step_prop += k_eff
                    step_acc += m - 1
                    # roll back BOTH write cursors BEFORE emitting: an
                    # eos/max_new finish inside the emit loop frees the
                    # sequence, and the cursors must already sit at the
                    # accepted boundary when prefix registration walks the
                    # pages. Target wrote k_eff+1 tokens, m were real;
                    # the draft consumed k_eff-1 proposals, m-1 were real
                    # (a fully-accepted row needs no draft rollback — the
                    # bonus token leaves a 2-token catch-up lag instead).
                    with self.cache.lock:
                        self.cache.rollback(s.sid, (k_eff + 1) - m)
                    if s.draft_sid is not None:
                        over = max(k_eff - m, 0)
                        if over:
                            with self._draft_cache.lock:
                                self._draft_cache.rollback(s.draft_sid, over)
                            s.dlen -= over
                    if s.handle.trace is not None:
                        s.handle.trace.note_speculation(k_eff, m - 1)
                    for t in samples[:m]:
                        self._emit(s, int(t))
                        if s not in self._active:
                            break  # finished/cancelled mid-acceptance
                    continue
                if kind == "decode":
                    self._emit(s, tok)
                    continue
                s.filled += n
                if s.filled < s.handle.prompt.size:
                    continue  # mid-prompt chunk: sampled token is not real
                # prompt complete: stream the first token, then either join
                # the local decode batch or — prefill role — hand the chain
                # to the decode engine (prefix registration waits for
                # EVICTION either way: a still-generating sequence
                # registering its partial tail page would copy-on-write its
                # own next decode token, an extra page draw its admission
                # reservation never counted)
                self._prefilling.remove(s)  # lint-ok[unlocked-shared-state]: scheduler-thread-owned list; promote-to-active handoff stays on the one loop thread
                _monitor.histogram("serve.ttft_s").observe(
                    now - s.handle.t_submit)
                if self._handoff_fn is not None:
                    self._handoff_seq(s, tok)
                    continue
                self._active.append(s)  # lint-ok[unlocked-shared-state]: scheduler-thread-owned list; readers take GIL-atomic list() snapshots (load_report)
                self._emit(s, tok)
            self._spec_proposed += step_prop  # lint-ok[unlocked-shared-state]: loop-thread-owned monotonic counters, same contract as _attn_computed
            self._spec_accepted += step_acc  # lint-ok[unlocked-shared-state]: paired with _spec_proposed above
        with _stat.span("serve.step.telemetry"):
            # the serve record is exported AFTER the verdict so it can
            # carry this step's speculation outcome (zeros when off)
            rec["proposed_tokens"] = int(step_prop)
            rec["accepted_tokens"] = int(step_acc)
            rec["accept_rate"] = (step_acc / step_prop) if step_prop else 0.0
            _monitor.export_step(rec, kind="serve")
            self._note_kv_step()

    def _note_kv_step(self):
        """Per-step pool bookkeeping (loop thread, lint-fenced): track
        peak LIVE occupancy and emit the periodic `kind:"kvcache"`
        snapshot every kv_snapshot_every steps. Pure host dict math, no
        device reads, no per-token records. Evictable prefix-registry
        retention is subtracted — it is best-effort cache, reclaimed on
        demand, so counting it would drift the peak toward 1.0 on any
        long prefix-cached run regardless of real pressure (the
        registry walk is bounded by the pool size: one short host scan
        per ms-scale decode step)."""
        self._step_i += 1
        live = self.cache.n_pages - 1 - self.cache.n_free_pages() \
            - self.cache.n_evictable_pages()
        if live > self._kv_peak_held:
            self._kv_peak_held = live  # lint-ok[unlocked-shared-state]: loop-thread-owned peak watermark; kv_peak_occupancy's lock-free read tolerates one stale step
        if (self._step_i - 1) % self.kv_snapshot_every == 0:
            _obs.record_pool_stats(
                self.name, self.cache,
                extra={"queue_depth": len(self._pending),
                       "active": len(self._active)
                       + len(self._prefilling)})
            # co-located kind:"memory" record: the attribution split
            # plus this pool's occupancy, measured hbm byte gauges, and
            # the free-list fragmentation metric — same cadence as the
            # kvcache snapshot, so the two reconcile row-for-row
            _mobs.record_memory(source="serve", step=self._step_i,
                                engine=self.name, cache=self.cache)

    def kv_peak_occupancy(self):
        """Peak LIVE fraction of the usable page pool (pad page and
        evictable registry retention excluded) held at any step so far
        — the bench headline's KV occupancy."""
        return self._kv_peak_held / max(self.cache.n_pages - 1, 1)

    def load_report(self):
        """Instantaneous admission snapshot (the serving observatory's
        router interface — ROADMAP open item 3's load-aware admission
        consumes exactly this): queue depth, active slots, free /
        reserved / projected-admittable pages via the same
        `pages_needed`/`pages_drawn` math admission uses, and recent
        TTFT/TPOT tail percentiles from the process-global histograms.
        Callable from any thread; pure host reads (lint-fenced). The
        lock acquire is BOUNDED — a wedged decode loop holding _cv
        must not hang the debug bundle asking what it was doing."""
        if not self._cv.acquire(timeout=1.0):
            return {"engine": self.name,
                    "unavailable": "engine lock held > 1s (wedged?)"}
        try:
            pending = len(self._pending)
            seqs = list(self._active) + list(self._prefilling)
            stopping = self._stopping
        finally:
            self._cv.release()
        # POOL-wide reservations (claims ledger): what admission — on
        # THIS engine or any other sharing the pool — has promised but
        # not yet drawn; snapshot-copied internally, safe lock-free
        outstanding = self.cache.outstanding_claims()
        free = self.cache.n_free_pages()
        evictable = self.cache.n_evictable_pages()
        admittable = max(free + evictable - outstanding, 0)
        ttft = _monitor.get_metric("serve.ttft_s")
        tpot = _monitor.get_metric("serve.tpot_s")
        rep = {
            "engine": self.name, "stopping": stopping,
            "queue_depth": pending, "max_queue": int(self.max_queue),
            "active": len(seqs), "max_batch": self.max_batch,
            "slots_free": max(self.max_batch - len(seqs), 0),
            # strategy-appropriate capacity: for the recurrent strategy
            # the cache's page surface counts fixed-size STATE SLOTS
            # (pages_needed == 1 per sequence), so admittable_pages is
            # admittable sequences — the router's ranking math holds
            # unchanged
            "cache_strategy": self.cache_strategy,
            "free_pages": free, "evictable_pages": evictable,
            "reserved_pages": outstanding,
            "admittable_pages": admittable,
            "admittable_tokens": admittable * self.cache.page_size,
            "kv_peak_occupancy": self.kv_peak_occupancy(),
            "ttft_p50_s": ttft.percentile(50) if ttft else 0.0,
            "ttft_p99_s": ttft.percentile(99) if ttft else 0.0,
            "tpot_p50_s": tpot.percentile(50) if tpot else 0.0,
            "tpot_p99_s": tpot.percentile(99) if tpot else 0.0,
            # speculation quality (cumulative): the front door's fleet
            # snapshot surfaces accept_rate per engine
            "speculative": self._draft_cache is not None,
            "proposed_tokens": int(self._spec_proposed),
            "accepted_tokens": int(self._spec_accepted),
            "accept_rate": (self._spec_accepted / self._spec_proposed)
            if self._spec_proposed else 0.0,
        }
        # measured-bytes admission feed next to the page math: the
        # pool's device arrays priced in bytes (free + evictable pages
        # x measured per-page bytes; headroom subtracts outstanding
        # claims). The router's fleet rollup sums these over UNIQUE
        # pools so a disaggregated pair is not double-counted.
        hbm = _mobs.pool_hbm(self.cache)
        rep["hbm_total_bytes"] = int(hbm.get("hbm_total_bytes", 0))
        rep["hbm_free_bytes"] = int(hbm.get("hbm_free_bytes", 0))
        rep["hbm_headroom_bytes"] = int(hbm.get("hbm_headroom_bytes", 0))
        if self.cache_strategy != "paged":
            # state-slot capacity gauges (RecurrentStateCache /
            # HybridCache pool_stats) — what "memory headroom" means
            # when sequences cost one constant blob each
            stats = self.cache.pool_stats()
            rep["state_bytes"] = stats["state_bytes"]
            rep["state_bytes_total"] = stats["state_bytes_total"]
            rep["free_slots"] = stats["free_slots"]
            rep["held_slots"] = stats["held_slots"]
        return rep

    def observatory_snapshot(self):
        """What a debug bundle records for this engine: the admission
        snapshot + the full pool observatory state."""
        return {"load_report": self.load_report(),
                "pool_stats": self.cache.pool_stats()}

    def compiled_texts(self):
        """{signature: optimized HLO} of every ragged-step executable
        compiled so far for this engine's model — the serving twin of
        TrainStep.compiled_text (inspection/tests: chip_smoke.py reads
        them for the kernels' tpu_custom_calls)."""
        return {sig: entry[0].as_text() for sig, entry in
                getattr(self.model, "_ragged_exec", {}).items()}

    def warm(self, prompt_len, max_new_tokens=None):
        """Blocking warm_async: AOT-compile every ragged signature one
        request of `prompt_len` touches. Returns the count compiled
        NOW (cache hits and already-warm signatures are free)."""
        from ..jit import warm as _warm
        handles = self.warm_async(prompt_len, max_new_tokens)
        _warm.join(handles)
        return sum(1 for h in handles if h.fresh)

    def warm_async(self, prompt_len, max_new_tokens=None):
        """Submit background AOT compiles for the (tokens, rows, table
        width) signatures a single request of `prompt_len` +
        max_new_tokens will dispatch — chunked prefill steps, every
        decode-step table-width bucket, AND the sub-chunk token
        buckets at each of those widths (a prefix-cache hit leaves a
        short prefill REMAINDER — e.g. one token of a 128-token prompt
        — which must not compile inline in the scheduler loop on
        exactly the traffic prefix caching optimizes). Steady-state
        single-request traffic, prefix-hit remainders at these widths
        included, then adds ZERO executables (the executable-sharing
        warmup contract; the canonical gate workload asserts it).
        Returns jit.warm.WarmHandles; join with jit.warm.join."""
        if not self.ragged:
            return []
        from ..ops.pallas.attention_core import MIN_Q_TOKENS
        max_new = self.default_max_new if max_new_tokens is None \
            else int(max_new_tokens)
        P = self.cache.page_size
        if self.cache_strategy == "recurrent":
            # fixed-size state slots: no page table, so the step's
            # width coordinate is constant — length never changes the
            # compiled signature (the strategy's whole point)
            def width(tokens):
                return 1
        else:
            def width(tokens):  # table width bucket once tokens held
                return self._pow2(-(-tokens // P))

        # every token bucket floors at MIN_Q_TOKENS — the same rule
        # _ragged_step pads with, so short chunks, prefix-hit
        # remainders, and decode steps all land on signatures warmed
        # here (small buckets COLLAPSE: a 4-prompt workload warms one
        # (8, 1, w) signature where the unfloored schedule warmed
        # (4,...) and (1,...) separately)
        sigs, filled, total = [], 0, int(prompt_len)
        while filled < total:
            n = min(self.prefill_chunk, total - filled)
            filled += n
            t_bucket = self._pow2(n)
            w = width(filled)
            while t_bucket >= 1:  # sub-chunk remainders at this width
                sigs.append((max(t_bucket, MIN_Q_TOKENS), 1, w))
                t_bucket //= 2
        for k in range(max_new - 1):  # decode k writes token total+k
            sigs.append((MIN_Q_TOKENS, 1, width(total + k + 1)))
        handles = [self.model.warm_ragged(self.cache, *sig)
                   for sig in dict.fromkeys(sigs)]
        if self._draft_cache is not None:
            # the DRAFT schedule: catch-up rows walk the prompt in
            # max(prefill_chunk, 2)-token chunks over the draft pool's
            # own width buckets (sub-chunk remainders included — the
            # post-bonus 2-token lag and the final partial chunk land
            # there), then 1-token proposal steps out to
            # prompt + max_new + k held tokens. The verify rows
            # themselves need nothing new: k+1 <= MIN_Q_TOKENS tokens
            # pad into the decode signatures warmed above.
            # Over-warming is harmless (the ledger only grows); a
            # steady-state draft compile is not.
            dc = self._draft_cache
            cap = max(self.prefill_chunk, 2)

            def dwidth(tokens):  # draft-pool width bucket
                return self._pow2(-(-tokens // dc.page_size))

            dsigs, dfilled = [], 0
            while dfilled < total:
                n = min(cap, total - dfilled)
                dfilled += n
                t_bucket = self._pow2(n)
                w = dwidth(dfilled)
                while t_bucket >= 1:
                    dsigs.append((max(t_bucket, MIN_Q_TOKENS), 1, w))
                    t_bucket //= 2
            for j in range(max_new + self.speculative.k):
                dsigs.append((MIN_Q_TOKENS, 1, dwidth(total + j + 1)))
            handles += [
                self.speculative.draft_model.warm_ragged(dc, *sig)
                for sig in dict.fromkeys(dsigs)]
        return handles

    def _emit(self, seq, tok):
        """Record one decoded token; stream it; evict on finish — or on
        caller cancel(), which must free the pages and the batch slot
        instead of decoding a sequence nobody is waiting for."""
        h = seq.handle
        if h.future.cancelled():
            with self.cache.lock:
                self.cache.free_sequence(seq.sid)
            self._free_draft(seq)
            self._active.remove(seq)  # lint-ok[unlocked-shared-state]: scheduler-thread-owned list (cancel eviction); remove() is C-level atomic under the GIL
            if h.trace is not None:  # tokens already generated = waste
                h.trace.finish("cancelled")
            h._close()
            with self._cv:
                self._cv.notify_all()  # pages freed: admission may proceed
            return
        if h.trace is not None:
            # idempotent: the TTFT boundary locally, and — for an
            # ADOPTED sequence, whose fresh decode-side trace has no
            # t_first yet even though seq.generated is non-empty — the
            # first local decode step of the handoff pair
            h.trace.first_token()
            h.trace.note_token(self.cache.pages_held(seq.sid))
        _monitor.counter("serve.generated_tokens").inc()
        seq.generated.append(tok)
        seq.last = tok
        seq.handle._push(tok)
        if (h.eos_token_id is not None and tok == h.eos_token_id) \
                or len(seq.generated) >= h.max_new_tokens:
            # register the finished prompt's pages for future sharers
            # BEFORE freeing: the sequence is done writing, so nobody
            # (itself included) will ever copy-on-write a registered
            # tail mid-reservation, and the registry hold keeps the
            # pages alive past free_sequence
            with self.cache.lock:
                if self.prefix_cache and seq.filled >= h.prompt.size:
                    self.cache.register_prefix(seq.sid, h.prompt)
                self.cache.free_sequence(seq.sid)
            self._free_draft(seq)
            self._active.remove(seq)  # lint-ok[unlocked-shared-state]: scheduler-thread-owned list (completion retirement); remove() is C-level atomic under the GIL
            _monitor.histogram("serve.latency_s").observe(
                time.perf_counter() - h.t_submit)
            if h.trace is not None:  # record exists before result lands
                h.trace.finish("completed")
            final = np.asarray(seq.generated, np.int64)  # hot-sync-ok: host int list, not a device read
            _resolve_future(h.future, final)
            h._close()
            with self._cv:
                self._cv.notify_all()  # pages freed: admission may proceed

    def _sync_retraces(self):
        """Fold the model's trace-time decode-compile counter (see
        GPTForCausalLM._paged_decode_jit) into serve.retraces, delta
        since the last sync. The steady-state health signal: a growing
        count means admit/evict is changing the compiled shapes —
        exactly what plan_decode(pad_to=) exists to prevent."""
        n = self._model_traces()
        if n > self._synced_traces:
            d = n - self._synced_traces
            self._synced_traces = n
            self.retraces += d
            _monitor.counter("serve.retraces").inc(d)

    def _fail_all(self, exc):
        """A decode-step failure poisons shared state (donated pools):
        fail every in-flight request loudly rather than hang them —
        queued adoptions included (their chains release back to the
        pool; the other engine of the pair may still be healthy)."""
        with self._cv:
            seqs = list(self._active) + list(self._prefilling)
            self._active, self._prefilling = [], []
            pend, self._pending = list(self._pending), deque()
            adopted, self._adopted = list(self._adopted), deque()
        for seq in seqs:
            try:
                with self.cache.lock:
                    self.cache.free_sequence(seq.sid)
            except Exception:
                pass
            self._free_draft(seq)
            _reject_future(seq.handle.future, exc)
            _finish_trace(seq.handle.trace, exc)
            seq.handle._close()
        for item in adopted:
            handle, chain = item[0], item[1]
            self._release_chain_pair(chain)
            _reject_future(handle.future, exc)
            _finish_trace(handle.trace, exc)
            handle._close()
        for h in pend:
            _reject_future(h.future, exc)
            _finish_trace(h.trace, exc)
            h._close()

    # -- lifecycle (drain/shutdown via _SchedulerLifecycle) --------------
    def _outstanding(self):
        return bool(self._pending or self._active or self._prefilling
                    or self._admitting or self._adopted)

    def _take_pending(self):
        self._abort = True  # the loop thread fails _active itself
        out = [(h, None, None) for h in self._pending]
        self._pending.clear()
        return out

    def _take_outstanding(self):
        # the loop thread is gone (or dying) with the engine, so the
        # _abort flag set by _take_pending has no reader — detach the
        # active set too or their handles hang forever. Queued
        # adoptions release their chains (draft riders included) back
        # to the (shared) pools.
        out = self._take_pending()
        out += [(s.handle, s.sid, s.draft_sid)
                for s in self._active + self._prefilling]
        self._active, self._prefilling = [], []
        while self._adopted:
            item = self._adopted.popleft()
            self._release_chain_pair(item[1])
            out.append((item[0], None, None))
        return out

    def _reject_detached(self, items, exc):
        for h, sid, dsid in items:
            if sid is not None:
                try:
                    self.cache.free_sequence(sid)
                except Exception:
                    pass
            self._free_draft_sid(dsid)
            _reject_future(h.future, exc)
            _finish_trace(h.trace, exc)
            h._close()
