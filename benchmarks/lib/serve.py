"""One serving cell, once: the program's GenerationEngine behind the
benchmark's load generator, every token stamped by the host clock as it
is handed to the client's stream, then a sample of the finished requests
held to the plain reference.

From the program: inference.GenerationEngine (submit, shutdown), the
model's warm_ragged, the cache object's n_pages / n_free_pages, and the
monitor's "serve.batch_size" histogram (one observation per scheduler
step). One private seam: GenerationHandle._push is wrapped to stamp the
time a token reaches the client's stream — the engine offers no public
per-token callback (listed for the tracing issue in PERF.md)."""
import gc
import queue
import time

import numpy as np

from . import correct as C
from . import program as P
from . import traffic as T

FIRST_TOKEN_WAIT_S = 60.0


def stamp_tokens():
    from paddle_tpu.inference.serving import GenerationHandle
    if getattr(GenerationHandle, "_bench_stamped", False):
        return
    push = GenerationHandle._push

    def stamped(self, tok):
        self.__dict__.setdefault("bench_t", []).append(time.perf_counter())
        return push(self, tok)

    GenerationHandle._push = stamped
    GenerationHandle._bench_stamped = True


class Sent:
    """One request as the load generator saw it."""
    __slots__ = ("prompt", "max_new", "due", "sent", "handle", "refused")

    def __init__(self, req, due):
        self.prompt, self.max_new = req["prompt"], req["max_new"]
        self.due, self.sent = due, None
        self.handle, self.refused = None, False

    @property
    def stamps(self):
        return [] if self.handle is None else \
            self.handle.__dict__.get("bench_t", [])

    def done_at(self):
        """When the last token arrived, if the request has finished."""
        h = self.handle
        if h is None or not h.future.done() or h.future.cancelled() \
                or h.future.exception() is not None:
            return None
        return self.stamps[-1] if self.stamps else None


class Load:
    """Submits requests to one engine and keeps what it saw."""

    def __init__(self, engine, tracer):
        self.engine, self.tracer = engine, tracer
        self.sent, self.occupancy, self.late = [], [], []
        self._sampled = -1.0

    def submit(self, req, due):
        from paddle_tpu.inference.serving import QueueFullError
        s = Sent(req, due)
        s.sent = time.perf_counter()
        self.late.append(max(s.sent - due, 0.0))
        try:
            with self.tracer.span("bench.submit"):
                s.handle = self.engine.submit(req["prompt"],
                                              max_new_tokens=req["max_new"])
        except QueueFullError:
            s.refused = True
        self.sent.append(s)
        return s

    def tick(self, elapsed, on_tick):
        """Once per turn of the generator's loop: the runner's hook, and
        at most every 10 ms a reading of the cache's occupancy."""
        on_tick(elapsed)
        if elapsed - self._sampled >= 0.01:
            self._sampled = elapsed
            c = self.engine.cache
            self.occupancy.append(1.0 - c.n_free_pages() / (c.n_pages - 1))

    def open_loop(self, schedule, seconds, on_tick):
        t0 = time.perf_counter()
        for req in schedule:
            due = t0 + req["t"]
            while True:
                now = time.perf_counter()
                self.tick(now - t0, on_tick)
                if now >= due or now - t0 >= seconds:
                    break
                with self.tracer.span("bench.wait"):
                    time.sleep(min(due - now, 0.02))
            if time.perf_counter() - t0 >= seconds:
                break
            self.submit(req, due)
        while time.perf_counter() - t0 < seconds:
            self.tick(time.perf_counter() - t0, on_tick)
            time.sleep(0.005)
        return t0

    def closed_loop(self, clients, seconds, on_tick):
        """Each client sends its next request when its last completes;
        completions reach this thread through a queue, so the engine's
        scheduler thread never runs the generator's code."""
        done = queue.Queue()
        nxt = [0] * len(clients)
        t0 = time.perf_counter()

        def send(ci):
            if nxt[ci] >= len(clients[ci]):
                return
            now = time.perf_counter()
            s = self.submit(clients[ci][nxt[ci]], now)
            nxt[ci] += 1
            if s.handle is not None:
                s.handle.future.add_done_callback(
                    lambda _f, ci=ci: done.put(ci))
            else:
                done.put(ci)

        for ci in range(len(clients)):
            send(ci)
        while True:
            left = seconds - (time.perf_counter() - t0)
            self.tick(seconds - left, on_tick)
            if left <= 0:
                break
            try:
                with self.tracer.span("bench.wait"):
                    ci = done.get(timeout=min(left, 0.02))
            except queue.Empty:
                continue
            send(ci)
        return t0


def drive(load, cell, vocab, seed, seconds, on_tick=lambda elapsed: None):
    tr = dict(cell["traffic"])
    if tr["loop"] == "open":
        tr["horizon_s"] = seconds
        return load.open_loop(T.open_loop(tr, vocab, seed), seconds, on_tick)
    need = int(seconds * tr["requests_per_client_per_s"]) + 2
    return load.closed_loop(T.closed_loop(tr, vocab, seed, need), seconds,
                            on_tick)


def wait_first_tokens(sent, timeout):
    end = time.perf_counter() + timeout
    for s in sent:
        while s.handle is not None and not s.stamps \
                and not s.handle.future.done():
            if time.perf_counter() > end:
                return
            time.sleep(0.002)


def percentile(values, p):
    if not values:
        raise RuntimeError("nothing to take a percentile of: the window "
                           "saw no request or no token gap")
    v = sorted(values)
    return v[min(len(v) - 1, int(np.ceil(p / 100.0 * len(v))) - 1)]


def rows_between(sent, a, b, chunk):
    """Row-steps (tokens in the row, context after it) that the engine
    ran in [a, b), as far as the client can tell: a decode token stamped
    in the interval is one row of one token; a prompt whose first token
    is stamped in it was prefilled in rows of `chunk` tokens."""
    rows = []
    for s in sent:
        st, p = s.stamps, len(s.prompt)
        if not st:
            continue
        if a <= st[0] < b:
            for start in range(0, p, chunk):
                n = min(chunk, p - start)
                rows.append((n, start + n))
        rows += [(1, p + j) for j, t in enumerate(st[1:], 1) if a <= t < b]
    return rows


def required_flops(config, rows):
    from . import work
    if config["reference"] == "gpt":
        return sum(work.gpt_forward_flops_token(config, c - n + (n + 1) / 2.0)
                   * n for n, c in rows)
    per = work.mamba_forward_flops_token(config)
    return per * sum(n for n, _ in rows)


def check_sample(sent, t_end, n_check, seed):
    """Requests finished inside the window: n_check of them drawn from
    the seed, the longest among them."""
    fin = [s for s in sent if (s.done_at() or t_end + 1) <= t_end]
    if not fin:
        return []
    longest = max(fin, key=lambda s: len(s.prompt) + len(s.stamps))
    rest = [s for s in fin if s is not longest]
    rng = np.random.RandomState((seed + 7) % (2 ** 32))
    pick = rng.choice(len(rest), size=min(n_check - 1, len(rest)),
                      replace=False) if rest else []
    return [longest] + [rest[i] for i in pick]


def reference_gaps(config, seed, sample, pad_to, tokens_to, prec="f32",
                   control=None):
    """sample: [(prompt ids, served ids)]. Per served token: the
    reference's best logit minus
    its logit of the served token. With `control` (a lower precision),
    the token judged at each position is instead the one the reference
    computed in that precision puts first. One row at a time, every row
    padded to `pad_to` ids and `tokens_to` served tokens, so one
    compiled program serves every run."""
    import jax
    import jax.numpy as jnp
    from ..references.common import weights_from_seed
    ref = P.reference_of(config)
    spec = ref.param_spec(config)
    w = weights_from_seed(spec, seed, config["dtype"])

    @jax.jit
    def row_gaps(w, ids, tokens, first, n):
        pos = first + jnp.arange(tokens.shape[0])
        lg = ref.forward(w, config, ids[None], prec)[0][pos]
        if control is not None:
            judged = ref.forward(w, config, ids[None], control)[0][pos] \
                .argmax(-1)
        else:
            judged = tokens
        gap = lg.max(-1) - jnp.take_along_axis(lg, judged[:, None], -1)[:, 0]
        live = jnp.arange(tokens.shape[0]) < n
        return jnp.where(live, gap, 0.0), jnp.where(live, gap == 0, True)

    max_new = int(tokens_to)
    gaps, exact = [], 0
    for prompt, out in sample:
        p, g = len(prompt), len(out)
        ids = np.zeros((pad_to,), np.int32)
        ids[:p], ids[p:p + g] = prompt, out
        toks = np.zeros((max_new,), np.int32)
        toks[:g] = out
        gp, ex = jax.device_get(row_gaps(w, ids, toks, p - 1, g))
        gaps += [float(x) for x in gp[:g]]
        exact += int(ex[:g].sum())
    return gaps, exact


def build_engine(cell, config, seed):
    import jax
    from paddle_tpu.inference import GenerationEngine
    from paddle_tpu.jit import warm as jwarm
    from ..references.common import weights_from_seed
    model = P.build_model(config)
    model.eval()
    spec = P.reference_of(config).param_spec(config)
    weights = weights_from_seed(spec, seed, config["dtype"])
    P.install_weights(model, weights)
    del weights
    eng = GenerationEngine(model, **cell["engine"])
    if not eng.ragged:
        raise RuntimeError("the engine did not take the ragged step")
    jwarm.join([model.warm_ragged(eng.cache, *sig)
                for sig in cell["signatures"]])
    return model, eng


def run(cell, config, devs, seed, seconds, trace, t_process, tracer):
    from paddle_tpu.profiler import monitor
    stamp_tokens()
    compiles = P.CompileCounter().install()
    vocab = config["vocab_size"]
    model, eng = build_engine(cell, config, seed)
    steps = monitor.histogram("serve.batch_size")
    try:
        # a few untimed seconds of the same traffic under another seed
        warm = Load(eng, tracer)
        drive(warm, cell, vocab, seed + 1, cell["warmup_seconds"])
        for s in warm.sent:      # the engine is empty when timing starts
            if s.handle is not None:
                s.handle.result(timeout=FIRST_TOKEN_WAIT_S)

        setup_s = time.perf_counter() - t_process
        load = Load(eng, tracer)
        steps0 = steps.count
        compiles.start()
        tracer.arm(trace, seconds)
        t0 = drive(load, cell, vocab, seed, seconds, tracer.tick)
        t_end = t0 + seconds
        n_steps = steps.count - steps0
        n_compiles = compiles.stop()
        tracer.stop()
        wait_first_tokens(load.sent, FIRST_TOKEN_WAIT_S)
        t_waited = time.perf_counter()
        sample = check_sample(load.sent, t_end, cell["correct"]["requests"],
                              seed)
        peak = P.memory_peak_bytes(devs)
    finally:
        eng.shutdown(wait=False)

    sent, late = load.sent, load.late
    # a request that never produced a token counts as the worst seen:
    # it waited from its due time to the end of the wait
    ttft = [(s.stamps[0] if s.stamps else t_waited) - s.due for s in sent]
    gaps_ms = [(b - a) * 1e3 for s in sent
               for a, b in zip(s.stamps, s.stamps[1:]) if b <= t_end]
    done = [s for s in sent if (s.done_at() or t_end + 1) <= t_end]
    out_tokens = sum(len(s.stamps) for s in done)
    failed = sum(1 for s in sent if not s.stamps)
    chunk = cell["engine"]["prefill_chunk"]
    rows = rows_between(sent, t0, t_end, chunk)
    window = {"kind": "serve", "window_s": seconds, "steps": n_steps,
              "tokens_processed": sum(n for n, _ in rows),
              "required_flops": required_flops(config, rows),
              "occupancy": load.occupancy, "compiles": n_compiles,
              "prefill_chunk": chunk,
              "page_size": cell["engine"].get("page_size", 16)}
    if trace:
        window["traced_rows"] = rows_between(sent, tracer.t_on, t_end, chunk)

    # ---- free the program, then the reference reads the sample
    del model, eng, load, warm
    gc.collect()
    t_ref = time.perf_counter()
    sample = [(np.asarray(s.prompt), np.asarray(s.handle.result(timeout=1)))
              for s in sample]
    if sample:
        gaps, exact = reference_gaps(config, seed, sample,
                                     cell["correct"]["pad_to"],
                                     cell["traffic"]["output"]["hi"])
        numbers = C.serve_numbers(gaps)
    else:
        gaps, exact = [], 0
        numbers = dict.fromkeys(cell["correct"]["limits"], 1e30)
    ok, rows_c = C.judge(numbers, cell["correct"]["limits"])
    p95 = percentile(ttft, 95) * 1e3
    return {
        "correct": ok and bool(sample), "rows": rows_c,
        "attempted": len(sent), "failed": failed,
        "end_to_end": {
            "serve_tokens_per_s": out_tokens / seconds,
            "ttft_p95_ms": p95, "itl_p95_ms": percentile(gaps_ms, 95),
            "setup_s": setup_s},
        "memory_peak_bytes": peak, "window": window,
        "sample": sample,
        "extra": {"reference_s": time.perf_counter() - t_ref,
                  "tokens_checked": len(gaps), "tokens_exact_argmax": exact,
                  "requests_completed": len(done),
                  "ttft_p50_ms": percentile(ttft, 50) * 1e3,
                  "itl_p50_ms": percentile(gaps_ms, 50),
                  "submit_late_p99_ms": percentile(late, 99) * 1e3},
    }
