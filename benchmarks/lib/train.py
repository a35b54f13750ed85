"""One training cell, once: build the program's compiled step with its
state, drive it from the seed through its first three steps, hand that
same object to the measured window, then hold what the three steps did
to the plain reference.

From the program: jit.TrainStep, optimizer.AdamW, io.device_prefetch —
called as a user calls them. Everything that measures or judges lives here."""
import collections
import gc
import time

import jax.numpy as jnp
import numpy as np

from ..references.common import seed_arg, weights_from_seed
from . import correct as C
from . import memory as M
from . import program as P
from .reftrain import STACKED, leafwise_norms, regenerated, unstack_norms

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


def readings_call(opt_state, spec, pick, seeded=None):
    """(held, norms): the arrays the step holds its optimizer state in,
    and the traced function norms(held, arg) -> reftrain.leafwise_norms
    of `pick` of each leaf's state, less the seeded weights that
    seeded(arg, flat) gives where `seeded` is given. Each leaf is read
    out of `held` inside the trace: on the fused path a leaf read out of
    the flat stores before the call is a copy, and so is a leaf read
    there in its own shape (the compiler lays the whole store out anew),
    so it is read as its flat slice."""
    flat = not isinstance(opt_state, dict)
    if flat:                    # the fused path's fused_update.LeafStateView
        held = opt_state._store
        view = lambda h: type(opt_state)(opt_state._epilogue, h)
    else:                                       # the tree epilogue
        held, view = opt_state, (lambda h: h)

    def norms(held, arg):
        state = view(held)

        def read(k, j):
            x = pick(state[k if j is None
                           else k.replace(STACKED, f".h.{j}.")])
            return x.reshape(-1) if flat else x
        return leafwise_norms(spec, read,
                              None if seeded is None else seeded(arg, flat))
    return held, norms


def state_norms(opt_state, spec, pick, phases=None, name="readings",
                seeded=None, arg=None):
    """{leaf: float} by readings_call, in ONE compiled call."""
    held, norms = readings_call(opt_state, spec, pick, seeded)
    arg = jnp.int32(0) if arg is None else arg
    exe = M.compiled(norms, held, arg)
    if phases is not None:
        phases.readings(name, exe)
    return unstack_norms(exe(held, arg))


def program_readings(step, w0, losses, hp, first_moment, phases=None):
    """What the program's first steps did, in the reference's terms: the
    first gradient as the optimizer got it (its first moment after one
    step is (1 - beta1) g), and how far the float32 master weights have
    moved from the seeded ones. `w0`: the seeded weights ({name: array},
    layers stacked), or (spec, seed, dtype), the seed that makes them
    again inside the call, one leaf at a time."""
    grad_norms = {k: n / (1.0 - hp["beta1"])
                  for k, n in first_moment.items()}
    if isinstance(w0, dict):
        spec = {k: (v.shape, None) for k, v in w0.items()}
        seeded, arg = (lambda w, flat: lambda i, k, done: w[k]), w0
    else:
        spec, seed, dtype = w0
        seeded = lambda s, flat: regenerated(spec, s, dtype, flat)
        arg = jnp.int32(seed_arg(seed))
    change = state_norms(step.opt_state, spec, lambda s: s["master"],
                         phases, "readings.change", seeded, arg)
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}


def run(cell, config, devs, seed, seconds, trace, t_process, tracer):
    from paddle_tpu.jit import warm as jwarm
    from .reftrain import reference_train
    hp = cell["optimizer"]
    B, S = cell["batch"], cell["seq"]
    compiles = P.CompileCounter().install()
    phases = M.Phases(devs)

    # ---- set-up: one object, its state from the seed, its first steps
    phases.start("setup")
    spec = P.reference_of(config).param_spec(config)
    model = P.build_model(config)
    P.install_weights(model, weights_from_seed(spec, seed, config["dtype"]))
    phases.mark("model")
    step = build_step(cell, model)
    phases.mark("state")
    batches = make_batches(seed, config["vocab_size"], B, S,
                           cell["distinct_batches"])
    it = feed(batches)
    x, y = next(it)
    warmed = step.warm(x, y)
    jwarm.join([warmed])
    step_exe = warmed.result()[0]
    phases.executable("train.step", step_exe)
    phases.mark("compiled")
    losses, first_moment = [], None
    for i in range(CHECK_STEPS):
        if i:
            x, y = next(it)
        losses.append(float(step(x, y).item()))
        phases.mark(f"step{i + 1}")
        if i == 0:
            first_moment = state_norms(
                step.opt_state, spec, lambda s: s["state"][0], phases,
                "readings.first_moment")
    prog = program_readings(step, (spec, seed, config["dtype"]), losses,
                            hp, first_moment, phases)
    phases.mark("readings")
    gc.collect()
    phases.end()
    phases.start("window")

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
    phases.executable("train.step", step_exe)
    phases.end()
    retraces = getattr(step, "retraces", None)

    # ---- free the program, then the reference follows the three steps
    del step, model, it, x, y, warmed, step_exe
    gc.collect()
    phases.start("reference")
    t_ref = time.perf_counter()
    ref = reference_train(P.reference_of(config), config, seed,
                          batches[:CHECK_STEPS], hp,
                          micro=cell["reference_micro_batch"],
                          phases=phases)
    phases.end()
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
                  "memory_by_phase": phases.out,
                  "losses": losses, "reference_losses": ref["losses"],
                  "retraces": retraces, **notes,
                  "not_compared": {k: v for k, v in numbers.items()
                                   if k not in cell["correct"]["limits"]}},
    }
