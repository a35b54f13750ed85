#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that paddle_tpu still starts on the
chip.

One process, which takes the chip itself, drives the main paths through
the entry points a user calls, at the full width of GPT-medium (and the
SSM family at its defaults), with random weights made from --seed:

  device     jax.devices(); anything but a TPU whose device_kind is in
             profiler/cost.py's peak table ends the run non-zero here
  train      models.gpt.gpt_medium() (24 layers, hidden 1024, vocab
             50,304) in bf16 with f32 master weights, batch 8 x seq 1024,
             jit.TrainStep + AdamW with flash attention, the fused update
             and the health vector left at their defaults: a warm-up and
             five steps on one fixed batch
  serve      the same GPT-medium behind inference.GenerationEngine
             (ragged step, paged KV, prefix cache): eight greedy requests
             of 32-512 prompt tokens and 32 new tokens each; every
             generated token checked against the plain forward of the
             same weights in float32, and every request against
             model.generate()
  serve-ssm  SSMForCausalLM at SSMConfig() defaults and one hybrid stack
             (attn_every=4) through GenerationEngine over the recurrent
             and hybrid cache strategies, four requests each
  --chips 4  ONLY the sharded phase and what it is compared with:
             fleet.init + HybridTrainStep (sharding=2 x mp=2, then dp=2 x
             mp=2) against the one-chip TrainStep of the same seed and
             batch, then where a ServingRouter's four replicas live

Every phase prints one JSON line; any failed check raises, so the exit
code is non-zero and the last line is never printed. The LAST line of a
good run is exactly
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

The seconds printed here are set-up evidence (does it start, how long do
cold and warm compiles take), not a benchmark: no rate is claimed.
"""
import argparse
import gc
import json
import math
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# the widths and counts of the real run are the phases' defaults; the
# CPU rehearsal (which imports this file and calls the phases, see
# .claude/skills/verify/SKILL.md) passes tiny ones
TRAIN_KERNELS = ("flash_attention_fwd", "flash_attention_dq",
                 "flash_attention_dkv", "fused_update_pass1",
                 "fused_update_pass2")
# (sharding, mp) of the --chips 4 meshes; dp takes the devices left
MESHES = ((2, 2), (1, 2))


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def emit(rec):
    print(json.dumps(rec), flush=True)


def memory_stats(dev):
    return dev.memory_stats() or {}


class PhaseMeter:
    """What one phase cost to set up, from the framework's own records
    (every jit/api.aot_compile lands in the compile observatory's
    ledger): seconds in trace+lower and in XLA, persistent-cache hits
    and misses, and the largest memory_analysis() total (arguments +
    outputs + temporaries - aliased) among the phase's executables —
    what the compiler says the biggest program needs. Beside it the
    allocator's own figures: `hbm_peak_bytes` is the PROCESS's
    high-water mark so far (JAX cannot reset it), so
    `hbm_peak_rise_bytes` says how far this phase pushed it: 0 means the
    phase stayed under an earlier phase's mark."""

    def __init__(self, dev):
        self.dev = dev

    def __enter__(self):
        from paddle_tpu.profiler import compile_observatory as co
        self._co = co
        self._records = []
        self._listener = co.add_listener(
            lambda ev: ev["phase"] == "done"
            and self._records.append(ev["record"]))
        self._t0 = time.perf_counter()
        self._peak0 = int(memory_stats(self.dev).get(
            "peak_bytes_in_use", 0))
        return self

    def __exit__(self, *exc):
        from paddle_tpu.framework import compile_cache as cc
        self._co.remove_listener(self._listener)
        recs = self._records
        st = memory_stats(self.dev)
        peak = int(st.get("peak_bytes_in_use", 0))
        self.report = {
            "seconds": round(time.perf_counter() - self._t0, 2),
            "compile_seconds": round(
                sum(r["lower_s"] + r["compile_s"] for r in recs), 2),
            "lower_seconds": round(sum(r["lower_s"] for r in recs), 2),
            "cache_hits": sum(r["cache_hit"] for r in recs),
            "cache_misses": sum(not r["cache_hit"] for r in recs),
            "cache_entries": cc.cache_entry_count(),
            "compiled_peak_memory_bytes": int(max(
                (r["peak_memory_bytes"] for r in recs), default=0)),
            "hbm_peak_bytes": peak,
            "hbm_peak_rise_bytes": peak - self._peak0,
            "hbm_bytes_in_use": int(st.get("bytes_in_use", 0))}
        return False


def kernel_calls(text, names):
    """How many tpu_custom_calls of each named Pallas kernel a compiled
    program's text holds (the kernels carry stable names)."""
    lines = [l for l in text.splitlines() if "tpu_custom_call" in l]
    return {n: sum(1 for l in lines if n in l) for n in names}


def require_kernels(text, names, what):
    found = kernel_calls(text, names)
    missing = [n for n, c in found.items() if c == 0]
    check(not missing, f"{what}: compiled step holds no tpu_custom_call "
          f"of {missing} — a kernel gave way to a composition")
    return found


def loss_fn(logits, labels):
    import paddle_tpu.nn as nn
    V = logits.shape[-1]
    return nn.functional.cross_entropy(
        logits.reshape([-1, V]), labels.reshape([-1]))


# ---------------------------------------------------------------- device
def phase_device(want_count):
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        print(f"chip_smoke: JAX found no accelerator (platform "
              f"{d.platform!r}); this script proves nothing off the chip",
              file=sys.stderr, flush=True)
        raise SystemExit(2)
    from paddle_tpu.profiler.cost import PEAK_BF16_FLOPS
    kind = d.device_kind
    check(any(k in kind.lower() for k in PEAK_BF16_FLOPS),
          f"device kind {kind!r} is not in profiler/cost.py "
          "PEAK_BF16_FLOPS — no default peak is assumed")
    check(len(devs) == want_count,
          f"expected {want_count} chip(s), JAX reports {len(devs)}")
    from paddle_tpu.framework import compile_cache as cc
    emit({"phase": "device", "platform": d.platform, "kind": kind,
          "count": len(devs), "jax": jax.__version__,
          "compile_cache_dir": cc.cache_dir(),
          "compile_cache_entries_at_start": cc.cache_entry_count()})
    return {"platform": d.platform, "kind": kind, "count": len(devs)}


# ----------------------------------------------------------------- train
def build_gpt(cfg, seed, bf16=True):
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTForCausalLM
    paddle.seed(seed)
    model = GPTForCausalLM(cfg)
    if bf16:
        model.bfloat16()
    return model


def fixed_batch(seed, vocab, batch, seq):
    """One seeded batch of random tokens and their next tokens (random
    next tokens are independent of the input, so an untrained model's
    loss is ln(vocab))."""
    import paddle_tpu as paddle
    toks = np.random.RandomState(seed).randint(
        0, vocab, size=(batch, seq + 1)).astype(np.int32)
    return paddle.to_tensor(toks[:, :-1]), paddle.to_tensor(toks[:, 1:])


def train_steps(step, xy, n):
    """n steps on the one fixed batch; each loss is fetched, which is
    the barrier."""
    return [float(step(*xy).item()) for _ in range(n)]


def phase_train(model, dev, seed, batch, seq, kernels=TRAIN_KERNELS,
                n_steps=5):
    from paddle_tpu import optimizer as opt
    from paddle_tpu.jit import TrainStep, warm as jwarm
    cfg = model.cfg
    with PhaseMeter(dev) as meter:
        o = opt.AdamW(learning_rate=1e-4, parameters=model.parameters(),
                      multi_precision=True)
        step = TrainStep(model, loss_fn, o, monitor_health=True)
        xy = fixed_batch(seed, cfg.vocab_size, batch, seq)
        jwarm.join([step.warm(*xy)])            # compile = set-up
        found = require_kernels(step.compiled_text(*xy), kernels,
                                "train")
        t0 = time.perf_counter()
        losses = train_steps(step, xy, 1 + n_steps)   # warm-up + n
        steps_s = time.perf_counter() - t0
        health = step.flush_health() or {}
    # an untrained model's logits are not flat: they have variance
    # s2 = hidden x initializer_range^2 (0.41 here), which adds s2 / 2
    # to the ln(vocab) of a uniform guess
    s2 = cfg.hidden_size * cfg.initializer_range ** 2
    want = math.log(cfg.vocab_size) + s2 / 2
    check(all(math.isfinite(l) for l in losses),
          f"train: non-finite loss {losses}")
    check(abs(losses[0] - want) < 0.2,
          f"train: first loss {losses[0]:.4f} not within 0.2 of "
          f"ln({cfg.vocab_size}) + {s2 / 2:.3f} = {want:.4f}")
    check(losses[-1] < losses[0],
          f"train: loss did not fall over {n_steps} steps: {losses}")
    check(step.retraces == 1, f"train: {step.retraces} compiles, want 1")
    emit({"phase": "train", **meter.report,
          "layers": cfg.num_layers, "hidden": cfg.hidden_size,
          "vocab": cfg.vocab_size, "batch": batch, "seq": seq,
          "scan_remat": cfg.scan_remat,
          "steps_seconds": round(steps_s, 3), "losses": losses,
          "grad_norm": health.get("grad_norm"), "kernels": found})
    return losses


# ----------------------------------------------------------------- serve
def make_prompts(rng, n, lo, hi, vocab):
    lens = rng.randint(lo, hi + 1, size=n)
    return [rng.randint(0, vocab, size=int(k)).astype(np.int32)
            for k in lens]


def run_engine(model, prompts, new_tokens, **engine_kw):
    from paddle_tpu.inference import GenerationEngine
    eng = GenerationEngine(model, **engine_kw)
    check(eng.ragged, "engine did not take the ragged step")
    try:
        handles = [eng.submit(p, max_new_tokens=new_tokens)
                   for p in prompts]
        outs = [np.asarray(h.result(timeout=1100)) for h in handles]
    finally:
        eng.shutdown()
    for p, o in zip(prompts, outs):
        check(o.shape == (new_tokens,),
              f"request of {p.size} prompt tokens returned {o.shape}")
    return eng, outs


def ragged_kernels(eng, kernels, what):
    """Every ragged-step executable the run compiled holds each kernel's
    tpu_custom_call. Returns (signatures, total calls per kernel)."""
    texts = eng.compiled_texts()
    found = dict.fromkeys(kernels, 0)
    for text in texts.values():
        for k, c in require_kernels(text, kernels, what).items():
            found[k] += c
    return len(texts), found


def plain_logits(model, ids, first_rows, n_new):
    """The model's plain (non-paged) forward over one padded batch —
    one compile; the stack is causal, so the padding changes nothing
    before it. Returns float32 [n, n_new, V]: for request i the logits
    at rows first_rows[i] .. first_rows[i] + n_new - 1, the ones its
    n_new generated tokens were picked from."""
    import paddle_tpu as paddle
    full = paddle.jit.to_static(model)(paddle.to_tensor(ids)).numpy()
    return np.stack([np.asarray(full[i, r:r + n_new], np.float32)
                     for i, r in enumerate(first_rows)])


def reference_logits(model, prompts, outs):
    """Teacher-forced logits for every generated token — prompt +
    generated tokens fed through the plain forward — taken twice:
      own  the model as it was served (its dtype, default precision);
      ref  the SAME weights cast to float32, every matmul at the
           highest precision: what the weights say, free of the
           rounding of the dtype they were served in.
    Casts `model` to float32 in place (its serving is over).
    Returns (own, ref), each float32 [n, new, V]."""
    import jax
    n_new = outs[0].size
    total = max(p.size for p in prompts) + n_new
    total = -(-total // 128) * 128      # flash blocks want a round seq
    ids = np.zeros((len(prompts), total), np.int32)
    for i, (p, o) in enumerate(zip(prompts, outs)):
        ids[i, :p.size] = p
        ids[i, p.size:p.size + n_new] = o
    rows = [p.size - 1 for p in prompts]
    own = plain_logits(model, ids, rows, n_new)
    model.float()
    with jax.default_matmul_precision("highest"):
        ref = plain_logits(model, ids, rows, n_new)
    return own, ref


def check_greedy(name, outs, own, ref):
    """Every token the engine generated is the float32 reference's
    argmax, or loses to it by no more than rounding can hide. What
    rounding hides is MEASURED, not assumed: `noise` is the largest
    |own - ref| over the two top reference candidates of every scored
    position — how far the model's own plain forward, in the dtype it
    was served in, strays from its float32 self, on a path with no
    engine and no paged kernel in it. Two logits each off by `noise`
    can swap only if they are within 2 x noise, so that (or the issue's
    2e-2, where it is more) bounds a near-tie; a WRONG token among
    random-weight logits sits whole units below the top. Returns the
    record's fields and the bound."""
    n, n_new, _ = ref.shape
    picked = np.stack(outs)[..., None]                        # [n, new, 1]
    top2 = np.argsort(ref, axis=-1)[..., -2:]
    take = lambda a, idx: np.take_along_axis(a, idx, axis=-1)
    noise = float(np.abs(take(own, top2) - take(ref, top2)).max())
    bound = max(2e-2, 2.0 * noise)
    gaps = ref.max(axis=-1) - take(ref, picked)[..., 0]        # [n, new]
    bad = np.argwhere(gaps > bound)
    check(bad.size == 0,
          f"{name}: {len(bad)} generated tokens lose to the float32 "
          f"reference's argmax by more than {bound:.4f} (2 x measured "
          f"rounding noise {noise:.4f}); worst {float(gaps.max()):.4f}, "
          f"first at request/step {bad[:1].tolist()}")
    return {"tokens_checked": int(gaps.size),
            "tokens_exact_argmax": int((gaps == 0).sum()),
            "tokens_near_tie": int((gaps > 0).sum()),
            "tokens_beyond_2e-2": int((gaps > 2e-2).sum()),
            "max_logit_gap": float(gaps.max()),
            "rounding_noise": noise, "near_tie_bound": bound}, bound


def served(name, model, dev, prompts, new_tokens, kernels, engine_kw):
    """Requests through a GenerationEngine and the kernels in every
    ragged executable it compiled. Returns (the phase's record so far,
    the generated tokens)."""
    with PhaseMeter(dev) as meter:
        eng, outs = run_engine(model, prompts, new_tokens, **engine_kw)
        n_sigs, found = ragged_kernels(eng, kernels, name)
    cfg = model.cfg
    return {"phase": name, **meter.report, "layers": cfg.num_layers,
            "hidden": cfg.hidden_size, "requests": len(prompts),
            "new_tokens": new_tokens,
            "prompt_tokens": [int(p.size) for p in prompts],
            "ragged_signatures": n_sigs, "kernels": found,
            "cache_strategy": eng.cache_strategy}, outs


def verified(name, model, prompts, outs):
    """check_greedy against the float32 reference (which casts `model`:
    call it last). Returns (the record's fields, ref, bound)."""
    t0 = time.perf_counter()
    own, ref = reference_logits(model, prompts, outs)
    agree, bound = check_greedy(name, outs, own, ref)
    agree["reference_seconds"] = round(time.perf_counter() - t0, 2)
    return agree, ref, bound


def generate_agreement(prompts, outs, gens, ref, bound):
    """The engine's tokens against model.generate()'s, for EVERY
    request. Two bf16 paths part at the first near-tie and are different
    sequences from there on, so what is held to is: equal tokens up to
    the first difference, and at it two tokens whose float32 reference
    logits are within the near-tie bound."""
    n_new = outs[0].size
    lead = []
    for i, (p, g, o) in enumerate(zip(prompts, gens, outs)):
        diff = np.nonzero(g != o)[0]
        j = int(diff[0]) if diff.size else n_new
        lead.append(j)
        if diff.size:
            gap = abs(float(ref[i, j, g[j]] - ref[i, j, o[j]]))
            check(gap <= bound,
                  f"serve: request of {p.size} prompt tokens parts from "
                  f"model.generate() at step {j} on reference logits "
                  f"{gap:.4f} apart — not a near-tie")
    return {"generate_equal_leading_tokens": lead,
            "generate_requests_equal_throughout":
                sum(j == n_new for j in lead),
            "generate_requests_equal_first_8":
                sum(j >= min(8, n_new) for j in lead)}


def phase_serve(model, dev, seed, n_requests=8, prompt_lo=32,
                prompt_hi=512, new_tokens=32,
                kernels=("ragged_paged_attention",), **engine_kw):
    import paddle_tpu as paddle
    model.eval()
    prompts = make_prompts(np.random.RandomState(seed + 1), n_requests,
                           prompt_lo, prompt_hi, model.cfg.vocab_size)
    rec, outs = served("serve", model, dev, prompts, new_tokens, kernels,
                       engine_kw)
    # model.generate() on the static-cache path, greedy (temperature 0)
    t0 = time.perf_counter()
    gens = [np.asarray(model.generate(
        paddle.to_tensor(p[None]), max_new_tokens=new_tokens,
        temperature=0.0).numpy())[0, p.size:] for p in prompts]
    generate_s = round(time.perf_counter() - t0, 2)
    agree, ref, bound = verified("serve", model, prompts, outs)
    emit({**rec, **agree,
          **generate_agreement(prompts, outs, gens, ref, bound),
          "generate_seconds": generate_s})


def phase_serve_ssm(dev, seed, cfg, name, n_requests=4, prompt_lo=16,
                    prompt_hi=128, new_tokens=16, kernels=("ssm_scan",),
                    **engine_kw):
    import paddle_tpu as paddle
    from paddle_tpu.models.ssm import SSMForCausalLM
    paddle.seed(seed)
    model = SSMForCausalLM(cfg)
    model.eval()
    prompts = make_prompts(np.random.RandomState(seed + 2), n_requests,
                           prompt_lo, prompt_hi, cfg.vocab_size)
    rec, outs = served(name, model, dev, prompts, new_tokens, kernels,
                       engine_kw)
    agree, _, _ = verified(name, model, prompts, outs)
    emit({**rec, **agree, "d_inner": cfg.d_inner,
          "attn_every": cfg.attn_every})


# ------------------------------------------------------------ four chips
def phase_sharded(model, devs, seed, batch, seq, n_steps=3,
                  kernels=TRAIN_KERNELS[:3]):
    """fleet.init + HybridTrainStep over REAL meshes — each (sharding,
    mp) of MESHES, dp taking the devices left: sharding=2 x mp=2, then
    dp=2 x mp=2 — against the one-chip TrainStep of the same seed and
    batch (same process, device 0)."""
    from paddle_tpu import optimizer as opt
    from paddle_tpu.jit import TrainStep
    cfg = model.cfg
    xy = fixed_batch(seed, cfg.vocab_size, batch, seq)

    def adamw():
        return opt.AdamW(learning_rate=1e-4,
                         parameters=model.parameters(),
                         multi_precision=True)

    with PhaseMeter(devs[0]) as one_meter:
        one = TrainStep(model, loss_fn, adamw(), monitor_health=True)
        ref = train_steps(one, xy, n_steps)
    emit({"phase": "train-one-chip", **one_meter.report, "losses": ref})
    del one
    gc.collect()
    for sharding, mp in MESHES:
        sharded_against(ref, model, adamw(), devs, xy, n_steps, kernels,
                        sharding, mp)
        gc.collect()


def sharded_against(ref, model, optimizer, devs, xy, n_steps, kernels,
                    sharding, mp):
    import jax
    from paddle_tpu.distributed import fleet
    with PhaseMeter(devs[0]) as meter:
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs["dp_degree"] = \
            len(devs) // (sharding * mp)
        strategy.hybrid_configs["mp_degree"] = mp
        strategy.hybrid_configs["sharding_degree"] = sharding
        fleet.init(is_collective=True, strategy=strategy)
        step = fleet.build_train_step(model, loss_fn, optimizer)
        losses = train_steps(step, xy, n_steps)
        text = step.compiled_text(*xy)
    mesh = {k: int(v) for k, v in step.mesh.shape.items() if v > 1}
    found = require_kernels(text, kernels, f"sharded {mesh}")
    colls = {c: text.count(f" {c}(") + text.count(f" {c}-start(")
             for c in ("all-reduce", "all-gather", "reduce-scatter",
                       "collective-permute", "all-to-all")}
    check(colls["all-reduce"] + colls["reduce-scatter"] > 0,
          f"sharded {mesh}: compiled step reduces nothing across "
          f"chips: {colls}")
    worst = max(abs(a - b) for a, b in zip(ref, losses))
    check(all(math.isfinite(l) for l in losses) and worst <= 2e-2,
          f"sharded {mesh} losses {losses} vs one-chip {ref}: "
          f"{worst:.4f} apart")
    holders = set()
    for arr in jax.tree.leaves(step.params):
        holders |= {s.device.id for s in arr.addressable_shards}
    stats = {d.id: memory_stats(d) for d in devs}
    in_use = {i: int(st.get("bytes_in_use", 0))
              for i, st in stats.items()}
    check(holders == {d.id for d in devs},
          f"sharded {mesh}: parameter shards on devices "
          f"{sorted(holders)} only")
    check(all(v > 0 for v in in_use.values()),
          f"sharded {mesh}: a device holds nothing: {in_use}")
    emit({"phase": "train-sharded", **meter.report, "mesh": mesh,
          "losses": losses, "one_chip_losses": ref,
          "max_loss_difference": worst, "collectives": colls,
          "kernels": found, "param_shard_devices": sorted(holders),
          "bytes_in_use_by_device": in_use,
          "hbm_peak_bytes_by_device": {
              i: int(st.get("peak_bytes_in_use", 0))
              for i, st in stats.items()}})


def phase_router(model, devs, n_replicas=4):
    """Where a ServingRouter's replicas live: constructed, looked at, shut
    down — nothing is placed here that the router does not place."""
    from paddle_tpu.inference import GenerationEngine
    from paddle_tpu.inference.frontdoor import ServingRouter
    model.eval()
    engines = [GenerationEngine(model, n_pages=64, name=f"replica{i}")
               for i in range(n_replicas)]
    router = ServingRouter(engines)
    try:
        where = {}
        for eng in router.engines:
            p = next(iter(eng.model.parameters())).value
            where[eng.name] = {
                "params": sorted(d.id for d in p.devices()),
                "page_pool": sorted(d.id for d in eng.cache.k[0].devices())}
    finally:
        for eng in engines:
            eng.shutdown()
    used = {i for w in where.values() for v in w.values() for i in v}
    emit({"phase": "router-placement", "replicas": n_replicas,
          "devices": len(devs), "where": where,
          "all_on_one_device": len(used) == 1})


# ------------------------------------------------------------------ main
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded train phase, its one-chip "
                         "comparison and the router's placement")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    device = phase_device(args.chips)
    import jax
    from paddle_tpu.models.gpt import gpt_medium
    from paddle_tpu.models.ssm import SSMConfig
    devs = jax.devices()
    model = build_gpt(gpt_medium(), args.seed)
    if args.chips == 4:
        phase_sharded(model, devs, args.seed, batch=8, seq=1024)
        phase_router(model, devs)
    else:
        phase_train(model, devs[0], args.seed, batch=8, seq=1024)
        gc.collect()                     # the training state is released
        phase_serve(model, devs[0], args.seed, n_pages=512)
        del model
        gc.collect()
        phase_serve_ssm(devs[0], args.seed, SSMConfig(), "serve-ssm")
        gc.collect()
        phase_serve_ssm(devs[0], args.seed, SSMConfig(attn_every=4),
                        "serve-ssm-hybrid",
                        kernels=("ssm_scan", "ragged_paged_attention"))
    emit({"phase": "total", "seconds": round(time.perf_counter() - t0, 2)})
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
