#!/usr/bin/env python
"""Shared plumbing for the compile-observatory ratchet gates
(tools/check_compile_budget.py, tools/check_fusion.py) — and the
canonical workload that produces their ledger.

The gates compare per-executable `kind:"compile"` records (the
compilation observatory's ledger, profiler/compile_observatory.py)
against the checked-in BASELINE_HLO.json. The ledger can come from any
metrics JSONL (`--ledger file.jsonl`), but the apples-to-apples source
is the CANONICAL WORKLOAD here: a fixed tiny GPT train step (per-step,
scanned run_steps, scanned accumulate), a two-bucket serving engine,
the ragged paged-attention serving step (serve.ragged_step: the
Pallas mixed prefill+decode program behind GenerationEngine), a
2-engine DISAGGREGATED ServingRouter (prefill/decode roles over one
shared page pool — the router tier adds zero executables and lands
real kind:"route" records in the tier-1-linted ledger), a
SPECULATIVE engine (1-layer draft, k=2 — the verify rows pad into the
warmed decode signature, so speculation too must add zero target
executables AND zero steady-state draft traces), and an SSM engine
(models/ssm.py over a RecurrentStateCache — the second model family's
O(1) cache strategy: same ragged tag, its own exec signature, serve
records stamped cache_strategy="recurrent"),
compiled cold (persistent cache off) on the single-device CPU backend —
same model, same shapes, same flags every run, so fusion counts and
bytes-accessed are deterministic and compile seconds are comparable.

    python tools/_gate_common.py --emit OUT.jsonl   # run the workload
                                                    # (in a clean child
                                                    # env — the gates
                                                    # spawn this)

The workload WARMS its executables through the background compile
pipeline (jit/warm.py: train.step / run_steps / accumulate and both
serving buckets lower+compile concurrently), then runs the steady-state
calls — which must add ZERO executables beyond the warmed set (the
executable-sharing warmup contract; the emit fails loudly otherwise).
The warm set's `kind:"warm"` record carries wall_s next to the sum of
per-executable seconds — the overlap evidence check_compile_budget.py
ratchets as the `warm_set` comparand.

BASELINE_HLO.json schema (v1):

    {"schema": "paddle_tpu.hlo_baseline.v1",
     "executables": {"<tag>": {"lower_s": .., "compile_s": ..,
                               "total_s": .., "fusion_count": N,
                               "bytes_accessed": B, "instructions": M,
                               "flops": F}, ...},
     "warm_set": {"wall_s": .., "sum_s": .., "n_executables": N}}

Ratcheting: the gates never loosen the baseline; `--update` rewrites an
entry only when the current run is BETTER (lower seconds / fewer
fusions / fewer bytes), so the checked-in numbers always record the
best this container has done — regressions compare against that.
"""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE_DEFAULT = os.path.join(REPO, "BASELINE_HLO.json")
BASELINE_SCHEMA = "paddle_tpu.hlo_baseline.v1"


class GateError(Exception):
    """A gate could not even produce numbers (workload crash, bad
    baseline) — distinct from a regression verdict."""


def load_baseline(path):
    with open(path) as f:
        payload = json.load(f)
    if not isinstance(payload, dict) or \
            not isinstance(payload.get("executables"), dict):
        raise GateError(f"{path}: not a {BASELINE_SCHEMA} baseline "
                        "(no 'executables' table)")
    return payload


def save_baseline(path, payload):
    import time
    payload["schema"] = BASELINE_SCHEMA
    payload["recorded_utc"] = time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                            time.gmtime())
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")


def _load_kind(path, kind):
    recs = []
    with open(path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError as e:
                raise GateError(f"{path}:{lineno}: not JSONL ({e})")
            if isinstance(rec, dict) and rec.get("kind") == kind:
                recs.append(rec)
    return recs


def load_compile_records(path):
    """The `kind:"compile"` records of one metrics JSONL file."""
    return _load_kind(path, "compile")


def load_warm_record(path):
    """The LAST `kind:"warm"` record of one metrics JSONL file (the
    warm-set wall-vs-sum evidence jit/warm.join exports), or None when
    the ledger carries none — a pre-warm-pipeline ledger stays a valid
    gate source for the per-executable comparisons."""
    recs = _load_kind(path, "warm")
    return recs[-1] if recs else None


def aggregate(records):
    """Per-tag rollup for the gates (plain JSON math, no framework
    import: a gate given --ledger must stay a milliseconds-fast diff).
    Unlike profiler/compile_observatory.aggregate (which SUMS seconds
    for attribution), the gate comparand is the tag's single SLOWEST
    compile — `lower_s`/`compile_s`/`total_s` are the components of
    that one record. A real run's ledger legitimately carries several
    signatures per tag (tail batch, eval dtype); N ordinary compiles
    must not add up to a fake budget regression, while one genuinely
    slow compile still trips it. Max fusion/bytes/instructions across
    signatures, cache_hit only when every compile hit."""
    out = {}
    for r in records:
        t = out.setdefault(r.get("tag", "?"), {
            "lower_s": 0.0, "compile_s": 0.0, "total_s": 0.0,
            "cache_hit": True, "signatures": 0, "fusion_count": 0,
            "bytes_accessed": 0.0, "instructions": 0, "flops": 0.0})
        lower = float(r.get("lower_s", 0.0))
        comp = float(r.get("compile_s", 0.0))
        if lower + comp >= t["total_s"]:
            t["lower_s"], t["compile_s"] = lower, comp
            t["total_s"] = lower + comp
        t["cache_hit"] = t["cache_hit"] and bool(r.get("cache_hit"))
        t["signatures"] += 1
        t["fusion_count"] = max(t["fusion_count"],
                                int(r.get("fusion_count", 0)))
        t["bytes_accessed"] = max(t["bytes_accessed"],
                                  float(r.get("bytes_accessed", 0.0)))
        t["instructions"] = max(t["instructions"],
                                int(r.get("instructions", 0)))
        t["flops"] = max(t["flops"], float(r.get("flops", 0.0)))
    return out


def run_workload(out_path, timeout=300):
    """Run the canonical workload in a CLEAN subprocess (CPU backend,
    single device, persistent cache off, metrics JSONL -> out_path) and
    return its aggregated per-tag ledger."""
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PADDLE_TPU_COMPILE_CACHE": "0",
        "PADDLE_TPU_METRICS_FILE": str(out_path),
        "PYTHONUNBUFFERED": "1",
        # the child is `python tools/_gate_common.py`, whose sys.path[0]
        # is tools/ — the repo root must be importable for paddle_tpu
        "PYTHONPATH": REPO + (os.pathsep + env["PYTHONPATH"]
                              if env.get("PYTHONPATH") else ""),
    })
    env.pop("PADDLE_TPU_DEBUG_DUMP", None)
    # determinism: one host device, whatever the parent (e.g. the
    # 8-device test harness) had configured
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "--xla_force_host_platform_device_count" not in f]
    env["XLA_FLAGS"] = " ".join(
        flags + ["--xla_force_host_platform_device_count=1"]).strip()
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--emit",
             str(out_path)],
            env=env, cwd=REPO, capture_output=True, text=True,
            timeout=timeout)
    except subprocess.TimeoutExpired as e:
        # infrastructure failure (exit 2), NOT a budget verdict (exit
        # 1): a wedged workload must not read as a named regression
        raise GateError(
            f"canonical workload hung past {timeout}s "
            f"(stderr tail: {(e.stderr or b'')[-500:]!r})") from None
    if proc.returncode != 0:
        raise GateError("canonical workload failed "
                        f"(rc={proc.returncode}):\n{proc.stderr[-2000:]}")
    return aggregate(load_compile_records(out_path))


def emit_workload():
    """The canonical workload body (runs in the child run_workload
    spawns; expects the env above to be set already).

    The full warm set — the three TrainStep program flavors, both
    serving buckets, and the ragged serving step's prefill+decode
    signatures — compiles OVERLAPPED through the background
    compile pipeline (jit/warm.py), exactly as a production startup
    would; `jit.warm.join` exports the `kind:"warm"` wall-vs-sum
    record the compile-budget gate ratchets. The steady-state calls
    then run against the warmed executables and must add ZERO compile
    records (the executable-sharing warmup contract) — violating that
    fails the emit, and therefore both gates, loudly."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu import optimizer as opt
    from paddle_tpu.jit import TrainStep, warm as jwarm
    from paddle_tpu.models.gpt import GPTForCausalLM, GPTConfig
    from paddle_tpu.profiler import compile_observatory as cobs

    paddle.seed(0)
    # scan_layers=True (the GPTConfig default) is deliberate: compile-
    # bound paths lower ONE block body, not num_layers of them
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                    num_heads=2, max_position_embeddings=16, dropout=0.0)
    model = GPTForCausalLM(cfg)
    o = opt.AdamW(learning_rate=1e-4, parameters=model.parameters())

    def loss_fn(logits, labels):
        V = logits.shape[-1]
        return nn.functional.cross_entropy(
            logits.reshape([-1, V]), labels.reshape([-1]))

    step = TrainStep(model, loss_fn, o)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, size=(2, 16)).astype(np.int32))
    stacked = paddle.to_tensor(
        np.stack([ids.numpy(), ids.numpy()]))

    from paddle_tpu.inference import (InferenceEngine, GenerationEngine,
                                      ServingRouter)
    paddle.seed(0)
    eng = InferenceEngine(nn.Linear(8, 8), batch_sizes=(1, 2),
                          name="canonical")
    x_serve = np.zeros((1, 8), np.float32)
    # the ragged serving executable (serve.ragged_step — the Pallas
    # mixed prefill+decode program): its own tiny GPT in eval mode so
    # the train step's donation traffic can't touch its param snapshot.
    # prompt 4 + max_new 3 at page_size 16 keeps the table width at 1,
    # and the MIN_Q_TOKENS=8 token-bucket floor (q-blocks must reach
    # the MXU's 8-row sublane tile) collapses the prefill chunk (T=4)
    # and the decode step (T=1) onto ONE signature: (8, 1, 1)
    paddle.seed(0)
    gen_model = GPTForCausalLM(cfg)
    gen_model.eval()
    gen = GenerationEngine(gen_model, n_pages=8, page_size=16,
                           max_batch=2, max_new_tokens=3,
                           name="canonical_gen")
    # the serving FRONT DOOR: a 2-engine disaggregated router (prefill
    # role -> decode role over ONE shared page pool) on the same model
    # and pool geometry as canonical_gen, so every ragged signature it
    # dispatches is already in the warm set — the router tier must add
    # ZERO executables, and tier-1 lints real kind:"route" records
    router = ServingRouter.disaggregated(
        gen_model, n_pages=8, page_size=16, max_batch=2,
        max_new_tokens=3, name="canonical_router")
    # SPECULATIVE decoding through the same ragged step
    # (inference/speculative.py): a 1-layer draft proposes k=2 tokens
    # and the target verifies them as one k+1-token row — which pads
    # into the SAME (8, 1, 1) signature as every other row above, so
    # the speculative engine must add ZERO target executables, and its
    # draft's own schedule compiles entirely inside the warm set
    from paddle_tpu.inference import SpeculativeConfig
    paddle.seed(1)
    draft_cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=1,
                          num_heads=2, max_position_embeddings=16,
                          dropout=0.0)
    draft_model = GPTForCausalLM(draft_cfg)
    draft_model.eval()
    spec = GenerationEngine(gen_model, n_pages=8, page_size=16,
                            max_batch=2, max_new_tokens=3,
                            name="canonical_spec",
                            speculative=SpeculativeConfig(draft_model,
                                                          k=2))
    # the SECOND MODEL FAMILY (models/ssm.py): an O(1)-cache SSM engine
    # through the SAME serve.ragged_step tag — its RecurrentStateCache
    # keys a distinct executable via cache.exec_signature(), warmed
    # here like every other signature, and its serve/request/kvcache
    # records stamp cache_strategy="recurrent" so tier-1 lints the
    # strategy-conditional schema rules against real records
    from paddle_tpu.models.ssm import SSMConfig, SSMForCausalLM
    paddle.seed(2)
    ssm_cfg = SSMConfig(vocab_size=128, hidden_size=32, num_layers=2,
                        d_state=8, d_conv=4, expand=2,
                        max_position_embeddings=16)
    ssm_model = SSMForCausalLM(ssm_cfg)
    ssm_model.eval()
    ssm = GenerationEngine(ssm_model, n_pages=8, page_size=16,
                           max_batch=2, max_new_tokens=3,
                           name="canonical_ssm")
    handles = [
        step.warm(ids, ids),                       # train.step
        step.warm_run_steps(2, ids, ids),          # train.run_steps
        step.warm_accumulate(2, stacked, stacked),  # train.accumulate
    ] + eng.warm_async(x_serve) \
      + gen.warm_async(4, 3) \
      + router.warm_async(4, 3) \
      + spec.warm_async(4, 3) \
      + ssm.warm_async(4, 3)                       # serve.ragged_step
    summary = jwarm.join(handles)                  # kind:"warm" record
    warmed = cobs.ledger_signatures()
    # the draft shares the target's RAGGED_TAG, so the ledger-pair
    # check alone cannot see a steady-state DRAFT compile — the
    # per-model trace counters can, and must not move either
    traces0 = getattr(gen_model, "_ragged_traces", 0) \
        + getattr(draft_model, "_ragged_traces", 0) \
        + getattr(ssm_model, "_ragged_traces", 0)

    # steady state over the warmed executables
    float(step(ids, ids).item())
    step.run_steps(2, ids, ids)
    float(step.accumulate(2, stacked, stacked).item())
    eng(x_serve)
    eng.shutdown()
    gen.submit(np.array([1, 2, 3, 4]), max_new_tokens=3).result(120)
    gen.shutdown()
    spec.submit(np.array([1, 2, 3, 4]), max_new_tokens=3).result(120)
    spec.shutdown()
    ssm.submit(np.array([1, 2, 3, 4]), max_new_tokens=3).result(120)
    ssm.shutdown()
    router.submit(np.array([1, 2, 3, 4]), max_new_tokens=3,
                  deadline_ms=120_000).result(120)
    router._fleet_mon.snapshot()  # force ONE kind:"fleet" record: the
    router.shutdown()             # cadence (5 s) never fires in-gate
    steady = cobs.ledger_signatures()
    if steady != warmed:
        raise AssertionError(
            "executable-sharing warmup contract violated: steady state "
            f"compiled {sorted(steady - warmed)} beyond the warmed set "
            f"(warm summary: {summary})")
    traces1 = getattr(gen_model, "_ragged_traces", 0) \
        + getattr(draft_model, "_ragged_traces", 0) \
        + getattr(ssm_model, "_ragged_traces", 0)
    if traces1 != traces0:
        raise AssertionError(
            "speculative steady state retraced the ragged step "
            f"({traces0} -> {traces1} model-level traces) — the draft "
            "schedule or the verify-row bucketing missed a signature")

    # the serving observatory contract: every request submitted to
    # either engine lands EXACTLY ONE schema-valid kind:"request"
    # record whose token counts reconcile with the engine counters,
    # and the generation engine snapshots its page pool
    # (kind:"kvcache") — in the same tier-1-exercised ledger the
    # compile gates read, so the lint sees real instances
    import json as _json
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import check_metrics_schema as _cms
    from paddle_tpu.profiler import monitor as _pmon
    mfile = os.environ["PADDLE_TPU_METRICS_FILE"]
    reqs = _load_kind(mfile, "request")
    kvs = _load_kind(mfile, "kvcache")
    routes = _load_kind(mfile, "route")
    schema_errs = [e for r in reqs + kvs + routes
                   for e in _cms.validate_line(_json.dumps(r))]
    if schema_errs:
        raise AssertionError(
            f"serving observatory records violate the schema: "
            f"{schema_errs[:5]}")
    by_engine = {}
    for r in reqs:
        by_engine.setdefault(r["engine"], []).append(r)
    # the router request's trace is born at the PREFILL engine's submit
    # and SPLITS at the handoff: the prefill half closes with outcome
    # "handoff", the decode half carries the request to its terminal —
    # four records, one per engine, same request_id on the router pair
    if sorted(by_engine) != ["canonical", "canonical_gen",
                             "canonical_router_decode",
                             "canonical_router_prefill",
                             "canonical_spec", "canonical_ssm"] or \
            any(len(v) != 1 for v in by_engine.values()):
        raise AssertionError(
            "expected exactly one request record per engine "
            f"(prefill+decode halves split), got "
            f"{[(k, len(v)) for k, v in sorted(by_engine.items())]}")
    pre_rec = by_engine["canonical_router_prefill"][0]
    dec_rec = by_engine["canonical_router_decode"][0]
    if pre_rec["outcome"] != "handoff" or \
            pre_rec.get("handoff_of") != "canonical_router_decode" or \
            dec_rec.get("handoff_of") != "canonical_router_prefill" or \
            pre_rec["request_id"] != dec_rec["request_id"]:
        raise AssertionError(
            "the disaggregated pair must cross-name each other via "
            "handoff_of under ONE request_id: "
            f"prefill {pre_rec}, decode {dec_rec}")
    if any(r["outcome"] != "completed" for r in reqs
           if r["outcome"] != "handoff"):
        raise AssertionError(
            f"canonical requests must complete, got "
            f"{[(r['engine'], r['outcome']) for r in reqs]}")
    gen_total = _pmon.get_metric("serve.generated_tokens")
    gen_total = int(gen_total.value) if gen_total else 0
    # terminal records only: the handoff half's tokens are re-counted
    # by the decode half (seeded at adoption)
    rec_total = sum(r["generated_tokens"] for r in reqs
                    if r["outcome"] == "completed")
    if rec_total != gen_total or rec_total != 12:  # 4 x max_new_tokens=3
        raise AssertionError(
            "request-record token counts do not reconcile with the "
            f"engine counters: records {rec_total}, "
            f"serve.generated_tokens {gen_total}, expected 12")
    # the speculative contract: the canonical_spec request carries the
    # schema-valid proposed/accepted trio with real proposals, every
    # NON-speculative record stamps zeros, and >= 1 kind:"serve" step
    # record from canonical_spec reports its verify-row verdict — so
    # tier-1 lints real speculative records in the same ledger
    spec_rec = by_engine["canonical_spec"][0]
    if spec_rec.get("proposed_tokens", 0) < 1 or \
            spec_rec["accepted_tokens"] > spec_rec["proposed_tokens"]:
        raise AssertionError(
            "the canonical_spec request must propose >= 1 draft token "
            f"and accept at most what it proposed: {spec_rec}")
    for r in reqs:
        if r["engine"] != "canonical_spec" and (
                r.get("proposed_tokens", 0) != 0
                or r.get("accepted_tokens", 0) != 0
                or r.get("accept_rate", 0.0) != 0.0):
            raise AssertionError(
                "non-speculative request records must stamp zero "
                f"speculative counts: {r['engine']} -> {r}")
    serves = _load_kind(mfile, "serve")
    spec_steps = [r for r in serves if r.get("engine") == "canonical_spec"
                  and r.get("proposed_tokens", 0) >= 1]
    if not spec_steps:
        raise AssertionError(
            "expected >= 1 kind:'serve' record from canonical_spec "
            "with proposed_tokens >= 1 (did the draft propose at all?)")
    # the cache-strategy contract: the SSM engine stamps every serve
    # record with its strategy (and its request/kvcache records with
    # the same — schema-validated above), so tier-1 exercises the
    # strategy-conditional rules against REAL recurrent records
    ssm_steps = [r for r in serves
                 if r.get("engine") == "canonical_ssm"
                 and r.get("cache_strategy") == "recurrent"]
    if not ssm_steps:
        raise AssertionError(
            "expected >= 1 kind:'serve' record from canonical_ssm "
            "stamped cache_strategy='recurrent', got "
            f"{[(r.get('engine'), r.get('cache_strategy')) for r in serves][:8]}")
    if by_engine["canonical_ssm"][0].get("cache_strategy") \
            != "recurrent":
        raise AssertionError(
            "the canonical_ssm request record must stamp its strategy: "
            f"{by_engine['canonical_ssm'][0]}")
    errs = [e for r in serves
            for e in _cms.validate_line(_json.dumps(r))]
    if errs:
        raise AssertionError(
            f"serve records violate the schema: {errs[:5]}")
    if pre_rec["generated_tokens"] != 1:
        raise AssertionError(
            "the prefill half streams exactly its first token before "
            f"handing off, got {pre_rec['generated_tokens']}")
    kv_engines = {r["engine"] for r in kvs}
    if not kvs or "canonical_gen" not in kv_engines:
        raise AssertionError(
            f"expected kind:'kvcache' snapshots from canonical_gen, "
            f"got {[(r.get('engine'), r.get('kind')) for r in kvs][:5]}")
    # the front-door contract: the one router request lands >= 1
    # "dispatched" decision on the prefill engine AND exactly one
    # "handoff" moving its chain to the decode engine with reconciling
    # page counts (the schema cross-checks ceil(tokens/page_size))
    outcomes = {r["outcome"] for r in routes}
    if not {"dispatched", "handoff"} <= outcomes:
        raise AssertionError(
            f"expected dispatched + handoff route records, got "
            f"{[(r.get('outcome'), r.get('engine')) for r in routes]}")
    hoffs = [r for r in routes if r["outcome"] == "handoff"]
    if len(hoffs) != 1 or \
            hoffs[0]["engine"] != "canonical_router_decode" or \
            hoffs[0]["from_engine"] != "canonical_router_prefill" or \
            hoffs[0]["chain_tokens"] != 4:
        raise AssertionError(
            f"handoff record does not match the canonical request: "
            f"{hoffs}")

    # the fleet-observatory contract: the one handed-off request lands
    # EXACTLY ONE schema-valid kind:"journey" record joining the route
    # decision and both request records under one request_id, with the
    # handoff gap MEASURED (export stamp -> adopt stamp, >= 0), and the
    # forced pre-shutdown snapshot emitted >= 1 schema-valid
    # kind:"fleet" record — all in the same ledger the gates read
    journeys = _load_kind(mfile, "journey")
    fleets = _load_kind(mfile, "fleet")
    errs = [e for r in journeys + fleets
            for e in _cms.validate_line(_json.dumps(r))]
    if errs:
        raise AssertionError(
            f"fleet-observatory records violate the schema: {errs[:5]}")
    if len(journeys) != 1:
        raise AssertionError(
            "expected exactly one kind:'journey' record for the one "
            f"handed-off request, got {len(journeys)}")
    j = journeys[0]
    if j["request_id"] != pre_rec["request_id"] or \
            j["request_id"] != hoffs[0].get("request_id") or \
            j["prefill_engine"] != "canonical_router_prefill" or \
            j["decode_engine"] != "canonical_router_decode":
        raise AssertionError(
            "the journey must join the route decision and both request "
            f"records under one request_id: {j}")
    if j["handoff_gap_s"] < 0 or j["outcome"] != "completed" or \
            j["generated_tokens"] != 3 or j["chain_tokens"] != 4:
        raise AssertionError(
            f"journey accounting does not match the canonical "
            f"request: {j}")
    if not fleets or any(r["router"] != "canonical_router"
                         for r in fleets):
        raise AssertionError(
            f"expected >= 1 kind:'fleet' snapshot from "
            f"canonical_router, got {fleets[:3]}")

    # the distributed-observatory contract: the canonical workload must
    # land ≥1 schema-valid kind:"collective" record (an eager
    # all_reduce + wait — the first call per op is always sampled) and
    # ≥1 kind:"rankstat" record (the train steps above emitted one at
    # the first-step cadence) in the same tier-1-exercised ledger, so
    # the lint sees real instances of both new kinds
    import paddle_tpu.distributed as dist
    from paddle_tpu.profiler import dist_observatory as _dobs
    ct = paddle.to_tensor(np.ones(1024, np.float32))
    dist.all_reduce(ct)
    dist.wait(ct)
    rs = _dobs.emit_rankstat(force=True)
    if rs is None:
        raise AssertionError("emit_rankstat produced no record")
    colls = _load_kind(mfile, "collective")
    rstats = _load_kind(mfile, "rankstat")
    if not colls or not rstats:
        raise AssertionError(
            f"expected >=1 kind:'collective' and >=1 kind:'rankstat' "
            f"record, got {len(colls)} / {len(rstats)}")
    errs = [e for r in colls + rstats
            for e in _cms.validate_line(_json.dumps(r))]
    if errs:
        raise AssertionError(
            f"distributed-observatory records violate the schema: "
            f"{errs[:5]}")
    ops = {r["op"] for r in colls}
    if "all_reduce" not in ops:
        raise AssertionError(
            f"expected an all_reduce collective record, got ops {ops}")
    roll = _dobs.collective_rollup()
    if roll.get("all_reduce", {}).get("bytes", 0) < 4096:
        raise AssertionError(
            f"collective rollup did not fold the all_reduce payload: "
            f"{roll}")

    # the fault-tolerance contract: one snapshot-then-write checkpoint
    # save + verified resume on the canonical train step, so tier-1
    # lints REAL kind:"ckpt" records (schema: phases sum <= total,
    # bytes > 0, verified flag) in the same ledger the gates read
    import shutil as _shutil
    import tempfile as _tempfile
    from paddle_tpu.distributed.checkpoint import CheckpointManager
    ck_dir = _tempfile.mkdtemp(prefix="gate_ckpt_")
    try:
        mgr = CheckpointManager(ck_dir, keep_last=2)
        step_before = step._step_i
        handle = mgr.save(step)
        handle.result(120)  # committed
        restored = CheckpointManager(ck_dir).restore(step)
        if restored != step_before:
            raise AssertionError(
                f"checkpoint resume restored step {restored}, expected "
                f"{step_before}")
        ckpts = _load_kind(mfile, "ckpt")
        saves = [r for r in ckpts if r.get("op") == "save"]
        restores = [r for r in ckpts if r.get("op") == "restore"]
        if not saves or not restores:
            raise AssertionError(
                f"expected kind:'ckpt' save+restore records, got "
                f"{[(r.get('op'), r.get('step')) for r in ckpts]}")
        errs = [e for r in ckpts
                for e in _cms.validate_line(_json.dumps(r))]
        if errs:
            raise AssertionError(
                f"ckpt records violate the schema: {errs[:5]}")
        if not saves[-1]["committed"] or not restores[-1]["verified"]:
            raise AssertionError(
                f"canonical checkpoint must commit and verify: "
                f"{saves[-1]}, {restores[-1]}")
        mgr.close()
    finally:
        _shutil.rmtree(ck_dir, ignore_errors=True)

    # the memory-observatory contract: the canonical workload lands
    # schema-valid kind:"memory" records from BOTH the train step
    # cadence (source "train", first step always) and a serving
    # engine's kvcache cadence (source "serve", carrying the pool's
    # occupancy + measured hbm gauges), and the kv-pool TAG's ledger
    # bytes reconcile with pool_stats() page counts x measured
    # per-page bytes to within page granularity — measured
    # attribution, not analytic claims
    mems = _load_kind(mfile, "memory")
    errs = [e for r in mems for e in _cms.validate_line(_json.dumps(r))]
    if errs:
        raise AssertionError(
            f"memory records violate the schema: {errs[:5]}")
    train_mems = [r for r in mems if r.get("source") == "train"]
    serve_mems = [r for r in mems if r.get("source") == "serve"]
    if not train_mems or not serve_mems:
        raise AssertionError(
            "expected >= 1 kind:'memory' record from BOTH the train "
            f"step path and a serving engine, got "
            f"{len(train_mems)} train / {len(serve_mems)} serve")
    if not any("params" in r.get("tags", {}) and
               r["tags"]["params"] > 0 for r in train_mems):
        raise AssertionError(
            "train memory records must attribute the params store "
            f"(tags of the first: {train_mems[0].get('tags')})")
    kv_serve = [r for r in serve_mems
                if "n_pages" in r and "page_bytes" in r
                and f"kv_pool.{r.get('engine')}" in r.get("tags", {})]
    if not kv_serve:
        raise AssertionError(
            "expected >= 1 serve memory record carrying its kv pool's "
            "n_pages/page_bytes next to the kv_pool tag, got "
            f"{[(r.get('engine'), sorted(r.get('tags', {}))) for r in serve_mems][:4]}")
    for r in kv_serve:
        tag_b = r["tags"][f"kv_pool.{r['engine']}"]
        pool_b = r["n_pages"] * r["page_bytes"]
        if abs(tag_b - pool_b) > r["page_bytes"]:
            raise AssertionError(
                "kv-pool ledger bytes do not reconcile with "
                f"pool_stats page math on {r['engine']}: tag "
                f"{tag_b} vs n_pages {r['n_pages']} x page_bytes "
                f"{r['page_bytes']} = {pool_b}")

    # the static-analysis contract: the canonical workload runs
    # paddlelint (tools/paddlelint.py — docs/STATIC_ANALYSIS.md) over
    # the repo and lands its findings as `kind:"lint"` records in the
    # same tier-1-exercised ledger the gates read. The repo must be
    # CLEAN (zero unsuppressed findings) and the ledger must carry >=1
    # schema-valid lint record (the suppressed findings with their
    # reasons — an empty lint section would mean the linter silently
    # stopped looking)
    import paddlelint as _plint
    lint_findings, _ = _plint.run_passes(REPO)
    unsup = [f for f in lint_findings if not f.suppressed]
    if unsup:
        raise AssertionError(
            f"paddlelint found {len(unsup)} unsuppressed finding(s) "
            f"at HEAD; first: {unsup[0].render()}")
    for lrec in _plint.records(lint_findings):
        _pmon.export_step(
            {k: v for k, v in lrec.items()
             if k not in ("ts", "rank", "kind")}, kind="lint")
    lints = _load_kind(mfile, "lint")
    if not lints:
        raise AssertionError(
            "expected >=1 kind:'lint' record in the canonical ledger "
            "(paddlelint emitted none — did the fileset walk break?)")
    errs = [e for r in lints for e in _cms.validate_line(_json.dumps(r))]
    if errs:
        raise AssertionError(
            f"lint records violate the schema: {errs[:5]}")
    if not any(r.get("suppressed") and r.get("reason") for r in lints):
        raise AssertionError(
            "expected at least one suppressed lint finding carrying "
            "its reason (the hot-sync allowlist alone guarantees "
            "several at HEAD)")


def format_row(tag, parts):
    return f"  {tag:<28} " + "  ".join(parts)


def main(argv):
    if argv[:1] == ["--emit"]:
        out = argv[1] if len(argv) > 1 else None
        if out and not os.environ.get("PADDLE_TPU_METRICS_FILE"):
            os.environ["PADDLE_TPU_METRICS_FILE"] = out
        emit_workload()
        n = len(load_compile_records(
            os.environ["PADDLE_TPU_METRICS_FILE"]))
        print(f"canonical workload: {n} compile records -> "
              f"{os.environ['PADDLE_TPU_METRICS_FILE']}", file=sys.stderr)
        return 0
    print(__doc__)
    return 2


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
