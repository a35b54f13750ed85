"""One training cell, once: build the program's compiled step with its
state, drive it from the seed through its first three steps, hand that
same object to the measured window, then hold what the three steps did
to the plain reference.

From the program: jit.TrainStep, optimizer.AdamW, io.device_prefetch —
called as a user calls them. Everything that measures or judges lives here."""
import collections
import gc
import time

import numpy as np

from . import correct as C
from . import program as P

CHECK_STEPS = 3


def make_batches(seed, vocab, batch, seq, n):
    """n host batches [batch, seq + 1] of token ids, every row
    different, from the seed."""
    rng = np.random.RandomState((seed + 1) % (2 ** 32))
    return [rng.randint(0, vocab, size=(batch, seq + 1)).astype(np.int32)
            for _ in range(n)]


def feed(batches):
    """The window's feed: the host batches, cycled, staged onto the
    device by the program's prefetch ring as (inputs, next tokens)."""
    import itertools
    import paddle_tpu as paddle
    from paddle_tpu.io.device_prefetch import device_prefetch_iterator

    def source():
        for toks in itertools.cycle(batches):
            yield (paddle.to_tensor(toks[:, :-1]),
                   paddle.to_tensor(toks[:, 1:]))
    return device_prefetch_iterator(source(), depth=2)


def loss_fn(logits, labels):
    import paddle_tpu.nn as nn
    V = logits.shape[-1]
    return nn.functional.cross_entropy(
        logits.reshape([-1, V]), labels.reshape([-1]))


def build_step(cell, model):
    from paddle_tpu import optimizer as opt
    hp = cell["optimizer"]
    o = opt.AdamW(learning_rate=hp["lr"], beta1=hp["beta1"],
                  beta2=hp["beta2"], epsilon=hp["epsilon"],
                  weight_decay=hp["weight_decay"],
                  parameters=model.parameters(), multi_precision=True)
    from paddle_tpu.jit import TrainStep
    return TrainStep(model, loss_fn, o)


def program_readings(step, w0, losses, hp, first_moment):
    """What the program's first steps did, in the reference's terms: the
    first gradient as the optimizer got it (its first moment after one
    step is (1 - beta1) g), and how far the float32 master weights have
    moved from the seeded ones."""
    import jax
    from .reftrain import leaf_norms
    grad_norms = {k: n / (1.0 - hp["beta1"])
                  for k, n in first_moment.items()}
    masters = {k: s["master"] for k, s in step.opt_state.items()}
    delta = jax.jit(lambda m, w: {k: m[k] - P.leaf_of(w, k) for k in m})(
        masters, w0)
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": leaf_norms(delta)}


def run(cell, config, devs, seed, seconds, trace, t_process, tracer):
    import jax
    from paddle_tpu.jit import warm as jwarm
    from .reftrain import leaf_norms, reference_train
    hp = cell["optimizer"]
    B, S = cell["batch"], cell["seq"]
    compiles = P.CompileCounter().install()

    # ---- set-up: one object, its state from the seed, its first steps
    from ..references.common import weights_from_seed
    spec = P.reference_of(config).param_spec(config)
    model = P.build_model(config)
    P.install_weights(model, weights_from_seed(spec, seed, config["dtype"]))
    step = build_step(cell, model)
    batches = make_batches(seed, config["vocab_size"], B, S,
                           cell["distinct_batches"])
    it = feed(batches)
    x, y = next(it)
    jwarm.join([step.warm(x, y)])
    losses, first_moment = [], None
    for i in range(CHECK_STEPS):
        if i:
            x, y = next(it)
        losses.append(float(step(x, y).item()))
        if i == 0:
            first_moment = leaf_norms(
                {k: s["state"][0] for k, s in step.opt_state.items()})
    prog = program_readings(
        step, weights_from_seed(spec, seed, config["dtype"]), losses, hp,
        first_moment)
    gc.collect()

    # ---- the window: the same object goes on from step 4
    setup_s = time.perf_counter() - t_process
    pending = collections.deque()
    host_s, n_steps, bad = [], 0, 0
    compiles.start()
    tracer.arm(trace, seconds)
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        tracer.tick(time.perf_counter() - t0)
        with tracer.span("bench.feed"):
            x, y = next(it)
        h0 = time.perf_counter()
        with tracer.span("bench.step"):
            pending.append(step(x, y))
        host_s.append(time.perf_counter() - h0)
        n_steps += 1
        if len(pending) > 2:       # at most two steps run ahead
            with tracer.span("bench.wait"):
                bad += not np.isfinite(float(pending.popleft().item()))
    while pending:
        bad += not np.isfinite(float(pending.popleft().item()))
    window_s = time.perf_counter() - t0
    tracer.stop()
    n_compiles = compiles.stop()
    it.close()
    peak = P.memory_peak_bytes(devs)
    retraces = getattr(step, "retraces", None)

    # ---- free the program, then the reference follows the three steps
    del step, model, it, x, y
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference_train(P.reference_of(config), config, seed,
                          batches[:CHECK_STEPS], hp,
                          micro=cell["reference_micro_batch"])
    numbers, notes = C.train_numbers(prog, ref)
    ok, rows = C.judge(numbers, cell["correct"]["limits"])
    ok = ok and bad == 0
    tokens = n_steps * B * S
    return {
        "correct": ok, "rows": rows, "attempted": n_steps, "failed": bad,
        "end_to_end": {"train_tokens_per_s": tokens / window_s,
                       "setup_s": setup_s},
        "memory_peak_bytes": peak,
        "window": {"kind": "train", "window_s": window_s, "steps": n_steps,
                   "tokens": tokens, "host_step_s": host_s, "batch": B,
                   "seq": S, "compiles": n_compiles},
        "extra": {"reference_s": time.perf_counter() - t_ref,
                  "losses": losses, "reference_losses": ref["losses"],
                  "retraces": retraces, **notes,
                  "not_compared": {k: v for k, v in numbers.items()
                                   if k not in cell["correct"]["limits"]}},
    }
