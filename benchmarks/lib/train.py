"""One training cell, once: build the program's compiled step with its
state, drive it from the seed through its first three steps, hand that
same object to the measured window, then hold what the three steps did
to the plain reference.

From the program: jit.TrainStep, optimizer.AdamW, io.device_prefetch —
called as a user calls them. Everything that measures or judges lives here.

The objective is the cell file's. With no "objective" key it is
next-token prediction: host batches [B, S+1] of ids, the step called as
step(inputs, next tokens) with the mean cross-entropy over every position
as its loss. With {"kind": "block_diffusion", "block_length": L,
"t_min": t0} (MDLM, arXiv:2406.07524; BD3-LM, arXiv:2503.09573) the host
batches are triples drawn from the seed (make_diffusion_batches) and the
contract with the program is:
  - the step is TrainStep(model, None, optimizer, model_returns_loss=True)
    called as step(noised, clean, weights): int32 [B, S], int32 [B, S],
    float32 [B, S];
  - the program's model returns the float32 scalar
    sum_i weights[i] * CE(its logits at noised position i, clean[i]) / (B S)
    — how it runs the 2S positions of [noised ; clean] under the
    BD3-LM mask is the program's business;
  - `tokens` counts the clean tokens consumed, steps x B x S: the doubled
    positions are the objective's own cost, for a configuration's MFU
    reader to count.
The reference scores the same arrays (reftrain.objective_loss)."""
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


OBJECTIVES = ("block_diffusion",)


def objective(cell, config):
    """The cell's training objective: None for next-token prediction (no
    "objective" key), else its dict, held to what it needs: a known kind,
    blocks that tile the sequence (and are the configuration's own, where
    it states a block_length), t_min in (0, 1], and the configuration's
    mask_token_id inside the vocabulary the ids are drawn over."""
    obj = cell.get("objective")
    if obj is None:
        return None
    if obj["kind"] not in OBJECTIVES:
        raise ValueError(f"unknown objective {obj['kind']!r}")
    L = obj["block_length"]
    if L < 1 or cell["seq"] % L:
        raise ValueError(f"block_length {L} does not divide seq "
                         f"{cell['seq']}")
    if config.get("block_length", L) != L:
        raise ValueError(f"block_length {L}: the configuration states "
                         f"{config['block_length']}")
    if not 0 < obj["t_min"] <= 1:
        raise ValueError(f"t_min {obj['t_min']} is not in (0, 1]")
    if not 0 <= config["mask_token_id"] < config["vocab_size"]:
        raise ValueError(f"mask_token_id {config['mask_token_id']} is "
                         f"outside the vocabulary of {config['vocab_size']}")
    return obj


def make_diffusion_batches(seed, vocab, batch, seq, n, block_length, t_min,
                           mask_id):
    """n host triples (noised, clean, weights) from the seed, in
    make_batches' own stream: clean int32 [batch, seq] ids over the
    vocabulary less the mask id; for each row's block of block_length
    tokens a t ~ U[t_min, 1); each token masked with probability its
    block's t; noised the mask id where masked and clean elsewhere;
    weights float32, 1/t where masked and 0 elsewhere."""
    rng = np.random.RandomState((seed + 1) % (2 ** 32))
    out = []
    for _ in range(n):
        clean = rng.randint(0, vocab - 1, size=(batch, seq))
        clean = (clean + (clean >= mask_id)).astype(np.int32)
        t = np.repeat(rng.uniform(t_min, 1.0, (batch, seq // block_length)),
                      block_length, axis=1)
        masked = rng.random_sample((batch, seq)) < t
        out.append((np.where(masked, mask_id, clean).astype(np.int32), clean,
                    np.where(masked, 1.0 / t, 0.0).astype(np.float32)))
    return out


def host_batches(cell, config, seed, n):
    """The cell's n host batches from the seed, by its objective."""
    obj = objective(cell, config)
    if obj is None:
        return make_batches(seed, config["vocab_size"], cell["batch"],
                            cell["seq"], n)
    return make_diffusion_batches(
        seed, config["vocab_size"], cell["batch"], cell["seq"], n,
        obj["block_length"], obj["t_min"], config["mask_token_id"])


def step_args(batch):
    """A host batch as the step's arguments: a token array [B, S+1] as
    (inputs, next tokens), an objective's tuple of arrays as it is."""
    return batch if isinstance(batch, tuple) else (batch[:, :-1],
                                                   batch[:, 1:])


def feed(batches):
    """The window's feed: the host batches, cycled, staged onto the
    device by the program's prefetch ring as the step's arguments."""
    import itertools
    import paddle_tpu as paddle
    from paddle_tpu.io.device_prefetch import device_prefetch_iterator

    def source():
        for batch in itertools.cycle(batches):
            yield tuple(paddle.to_tensor(a) for a in step_args(batch))
    return device_prefetch_iterator(source(), depth=2)


def loss_fn(logits, labels):
    import paddle_tpu.nn as nn
    V = logits.shape[-1]
    return nn.functional.cross_entropy(
        logits.reshape([-1, V]), labels.reshape([-1]))


def build_step(cell, model, **step_kw):
    """The program's compiled step for the cell's objective (see the
    module's docstring); `step_kw` goes to TrainStep."""
    from paddle_tpu import optimizer as opt
    hp = cell["optimizer"]
    o = opt.AdamW(learning_rate=hp["lr"], beta1=hp["beta1"],
                  beta2=hp["beta2"], epsilon=hp["epsilon"],
                  weight_decay=hp["weight_decay"],
                  parameters=model.parameters(), multi_precision=True)
    from paddle_tpu.jit import TrainStep
    if cell.get("objective") is not None:
        return TrainStep(model, None, o, model_returns_loss=True, **step_kw)
    return TrainStep(model, loss_fn, o, **step_kw)


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
    batches = host_batches(cell, config, seed, cell["distinct_batches"])
    it = feed(batches)
    args = next(it)
    warmed = step.warm(*args)
    jwarm.join([warmed])
    step_exe = warmed.result()[0]
    phases.executable("train.step", step_exe)
    phases.mark("compiled")
    losses, first_moment = [], None
    for i in range(CHECK_STEPS):
        if i:
            args = next(it)
        losses.append(float(step(*args).item()))
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
            args = next(it)
        h0 = time.perf_counter()
        with tracer.span("bench.step"):
            pending.append(step(*args))
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
    del step, model, it, args, warmed, step_exe
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
