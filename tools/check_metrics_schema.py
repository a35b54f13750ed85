#!/usr/bin/env python
"""Schema lint for paddle_tpu metrics JSONL exports.

The per-step metrics file (PADDLE_TPU_METRICS_FILE, written by
paddle_tpu/profiler/monitor.py export_step) is a contract between the
framework and whatever driver/dashboard tails it. This tool
is the contract's enforcement point: tests/test_telemetry.py runs it on
a freshly emitted file, so the schema can't silently drift.

Schema (documented in docs/OBSERVABILITY.md):

  every line    one JSON object, no blank interior lines required keys:
                  ts    number   unix seconds
                  rank  int      process rank (0 single-controller)
                  kind  str      record type ("step", "scan", ...)
  kind == "step" additionally requires:
                  step         int     optimizer step index (>= 1)
                  step_time_s  number  wall seconds attributed to the step
                  compile_s    number  trace+compile seconds (0 warm)
                  cache_hit    bool    executable came from a cache
                  peak_bytes   int     device memory high-water mark
                  flops        number  per-step FLOPs (XLA cost analysis;
                                       0.0 when unavailable)
                  mfu          number  in [0, ~1]; 0.0 when unknown
                  and optionally (fused multi-tensor epilogue,
                  ops/pallas/fused_update.py):
                  epilogue_bytes int   > 0 — analytic HBM traffic of the
                                       two fused update passes
                  epilogue_share number in [0, 1] — epilogue_bytes over
                                       the executable's cost_analysis
                                       bytes
  kind == "serve" (one record per dispatched serving batch —
                  paddle_tpu/inference/serving.py) additionally requires:
                  engine       str     emitting engine's name (non-empty;
                                       the per-engine key that keeps
                                       multi-engine JSONL attributable)
                  requests     int     requests fused into the batch (>= 1)
                  batch_size   int     real rows dispatched (>= 1)
                  bucket_batch int     ladder bucket the batch padded to
                                       (>= batch_size)
                  queue_depth  int     requests still waiting at dispatch
                  pad_tokens   int     padding elements dispatched (>= 0)
                  latency_s    number  mean submit->result latency of the
                                       batch's requests (generation
                                       decode batches: mean in-flight
                                       request age at the step)
                  and optionally:
                  pad_token_fraction number  in [0, 1] — measured
                                       fraction of the step's attention
                                       score slots outside any causal
                                       bound (ragged steps report only
                                       the intra-page remainder; the
                                       pad_tokens COUNTER is what the
                                       ragged path zeroes)
                  tokens, bucket_tokens int  >= 0 tokens of a ragged
                                       step, and the bucket they padded to
                  prefix_hits  int     >= 0 prompt tokens served from the
                                       refcounted prefix cache
                  shared_pages int     >= 0 KV pages with > 1 holder
                  chunked_prefill_tokens int  >= 0 prompt tokens admitted
                                       via chunked prefill this step
                  proposed_tokens / accepted_tokens int >= 0 — draft
                                       tokens proposed / accepted by
                                       this step's verify rows
                                       (speculative decoding,
                                       inference/speculative.py);
                                       accepted <= proposed, and a
                                       non-speculative step stamps
                                       zeros
                  accept_rate  number  in [0, 1]; must equal
                                       accepted/proposed (0.0 when
                                       nothing proposed)
                  cache_strategy str   paged | recurrent | hybrid —
                                       the engine's decode-cache
                                       strategy (inference/
                                       cache_strategy.py). Absent
                                       means "paged" (pre-strategy
                                       records stay valid). Stamped
                                       on serve / request / kvcache /
                                       route / journey records, where
                                       it switches the strategy-
                                       conditional rules below
  kind == "health" (one record per resolved health vector —
                  TrainStep/HybridTrainStep monitor_health=True)
                  additionally requires:
                  step          int            optimizer step (>= 1)
                  loss          number|str     non-finite values export
                  grad_norm     number|str     as their repr strings
                  param_norm    number|str     ("nan", "inf") because
                  update_ratio  number|str     bare NaN is not JSON;
                  found_inf     number|str     numeric values must be
                                               >= 0 (found_inf: 0 or 1)
  kind == "collective" (sampled per-collective timing — the
                  distributed observatory,
                  profiler/dist_observatory.py, fed by every
                  paddle.distributed collective wrapper) additionally
                  requires:
                  op           str     collective kind (psum,
                                       all_reduce, ...; non-empty)
                  group        str     process group / mesh axis label
                                       (non-empty)
                  bytes        int     payload bytes (>= 0)
                  wall_s       number  host wall seconds of the call
                                       (>= 0)
                  bw_gbps      number  derived bus bandwidth GB/s
                                       (>= 0 and FINITE — an infinite
                                       bandwidth means the zero-time
                                       guard upstream broke); 0 for
                                       traced insertions
                  and optionally:
                  traced       bool    trace-time insertion, not an
                                       eager execution
                  calls        int     >= 1 cumulative calls of this op
  kind == "rankstat" (periodic per-rank skew telemetry —
                  profiler/dist_observatory.py emit_rankstat)
                  additionally requires:
                  step         int     >= 0 optimizer step at emission
                  world_size   int     >= 1; the record's rank MUST be
                                       < world_size (a rank outside
                                       the world is a launch-env bug)
                  step_time_p50_s number >= 0 (train.step_s reservoir)
                  step_time_p99_s number >= p50 (up to rounding)
                  host_blocked_s  number >= 0
                  collective_wait_s number >= 0 cumulative eager
                                       collective wall
                  collective_wait_share number in [0, 1] — the share
                                       of stepped wall time spent
                                       waiting at eager collectives
                                       (cross-field: the share is
                                       capped by the step time it is
                                       measured against)
                  peak_bytes   int     >= 0 device memory high-water
                  and optionally:
                  clock_offset_s number  this rank's clock offset vs
                                       rank 0 (any sign)
                  steps_observed int   >= 0
  kind == "event" (structured anomaly/lifecycle events —
                  profiler/flight_recorder.record_event) additionally
                  requires:
                  event        str     non-empty event name
                                       (nan_detected, loss_spike,
                                       watchdog_expired, retrace, ...)
  kind == "compile" (one record per AOT-compiled executable signature —
                  profiler/compile_observatory.py, fed by
                  jit/api.aot_compile) additionally requires:
                  tag          str     non-empty executable tag
                                       (train.step, fleet.hybrid_step,
                                       serve.<engine>.batch<b>, ...)
                  signature    str     non-empty abstract-signature key
                  lower_s      number  trace+lower seconds (>= 0)
                  compile_s    number  XLA compile seconds (>= 0); a
                                       cache_hit record must be near
                                       zero (<= 10 s: a hit is a cache
                                       LOAD, never a real compile)
                  cache_hit    bool    persistent compile cache hit
                  instructions int     HLO instruction count (>= 0)
                  fusion_count int     HLO fusion ops (>= 0)
                  bytes_accessed number  XLA cost analysis (>= 0)
                  flops        number  XLA cost analysis (>= 0)
                  peak_memory_bytes number  memory-analysis peak (>= 0)
                  and optionally:
                  op_counts    dict    {op kind: count >= 0}
                  kernels      dict    {"<scope>/<Pallas kernel>":
                                       count >= 0}
  kind == "warm" (one record per resolved warm set —
                  paddle_tpu/jit/warm.py join) additionally requires:
                  n_executables int    handles in the set (>= 0)
                  compiled_now int     handles that ran a compile, in
                                       [0, n_executables]
                  cache_hits   int     of compiled_now, how many were
                                       persistent-cache loads, in
                                       [0, compiled_now]
                  wall_s       number  first submit -> last done (>= 0)
                  sum_s        number  Σ per-executable lower+compile
                                       seconds (>= 0); wall_s well
                                       under sum_s is the overlap proof
                  and optionally:
                  tags         list    executable tags (non-empty strs)
  kind == "lint" (one record per static-analysis finding —
                  tools/paddlelint.py, docs/STATIC_ANALYSIS.md;
                  suppressed findings are exported too: the ledger
                  accounts for every deliberate exemption)
                  additionally requires:
                  pass         str     pass name from the KNOWN set
                                       (lock-order, blocking-under-
                                       lock, unlocked-shared-state,
                                       use-after-donate, hot-sync,
                                       suppression)
                  rule         str     non-empty violated-rule slug
                  file         str     non-empty repo-relative path
                  line         int     >= 0 (0 = whole-file finding)
                  severity     str     error | warning
                  message      str     non-empty human verdict
                  suppressed   bool    exempted via lint-ok /
                                       hot-sync-ok / a pass region
                                       table; suppressed=true REQUIRES
                                       a non-empty `reason` string (a
                                       reasonless suppression is the
                                       exact failure mode the linter
                                       exists to prevent)
  kind == "seed" (one record per compile-cache seeding —
                  framework/compile_cache.seed_from) additionally
                  requires:
                  source          str  donated artifact dir (non-empty)
                  cache_dir       str  seeded cache dir (non-empty)
                  entries_seeded  int  entries copied in (>= 0)
                  entries_skipped int  already present (>= 0)
  kind == "ckpt" (one record per checkpoint save/restore/GC —
                  distributed/checkpoint.py CheckpointManager;
                  docs/FAULT_TOLERANCE.md) additionally requires:
                  op           str     save | restore | gc
                  step         int     >= 0 optimizer step
                  dir          str     non-empty checkpoint directory
  op == "save"    additionally:
                  snapshot_s   number  >= 0 on-device snapshot phase
                  serialize_s  number  >= 0 device->host reads (writer)
                  write_s      number  >= 0 shard-file + manifest IO
                  commit_s     number  >= 0 COMMIT + atomic rename
                  total_s      number  >= sum of the four phases (up to
                                       1 ms rounding: the phases run
                                       inside the save's wall window)
                  bytes        int     payload bytes; MUST be > 0 when
                                       committed (an empty committed
                                       checkpoint is a lie)
                  n_leaves     int     >= 1 when committed
                  committed    bool    the atomic rename happened
                  and across one file, committed save steps must be
                  NON-DECREASING per rank (a step counter running
                  backwards means resume restored the wrong thing)
  op == "restore" additionally:
                  verified     bool    manifest+checksums validated
                  fell_back    int     >= 0 partial/corrupt checkpoints
                                       skipped on the way
                  bytes        int     >= 0 payload read
                  total_s      number  >= 0
  op == "gc"      additionally:
                  removed      int     >= 1 checkpoints deleted
  kind == "request" (ONE record per request at its terminal state —
                  the serving observatory's lifecycle ledger,
                  profiler/serve_observatory.py) additionally requires:
                  engine       str     emitting engine (non-empty)
                  request_id   str     unique per request (non-empty)
                  outcome      str     completed | expired | rejected |
                                       error | cancelled | handoff
                                       (handoff = the prefill half of a
                                       disaggregated request; the decode
                                       engine opens a fresh record under
                                       the SAME request_id and the fleet
                                       observatory joins the pair into
                                       one kind:"journey" record)
                  rows         int     batch rows (>= 1; generation: 1)
                  prompt_tokens int    >= 0 (inference requests: 0)
                  prefix_hit_tokens int  >= 0, <= prompt_tokens
                  generated_tokens int >= 0; MUST be 0 for outcome
                                       rejected/expired (those die
                                       before decoding — nonzero means
                                       the accounting lies)
                  queue_s      number  submit -> claimed (>= 0)
                  latency_s    number  submit -> terminal (>= 0, and
                                       >= queue_s + prefill_s +
                                       decode_s up to rounding)
                  and optionally:
                  prefill_s / decode_s number >= 0 phase splits
                  prefill_chunks int   >= 0 chunked-prefill steps
                  peak_pages_held int  >= 0 KV pages high-water mark
                  max_new_tokens int   >= 1; generated_tokens <= it
                  deadline_s   number  >= 0 allotted budget (seconds;
                                       0 = already expired at submit)
                  deadline_met bool    completed within deadline_s
                  error        str     exception repr (outcome error)
                  ttft_s       number  >= 0 submit -> first token
                  slo_class    str     non-empty (router-stamped)
                  handoff_of   str     non-empty; the OTHER engine of a
                                       disaggregated pair (on the
                                       prefill record: the decode
                                       engine, and vice versa) — how
                                       tools/obs_report.py reconciles
                                       the pair's token counts
                  proposed_tokens / accepted_tokens int >= 0 —
                                       speculative-decoding counts for
                                       THIS request (accepted <=
                                       proposed, accepted <=
                                       generated_tokens; zeros when
                                       speculation is off)
                  accept_rate  number  in [0, 1] == accepted/proposed
                                       (0.0 when nothing proposed)
  kind == "route" (ONE record per routing decision — the serving
                  front door, paddle_tpu/inference/frontdoor.py
                  ServingRouter) additionally requires:
                  engine       str     engine chosen (non-empty; MUST
                                       be a member of `fleet` — a
                                       router placing work on an
                                       engine it does not know about
                                       is the bug this catches)
                  fleet        list    the router's engine names
                                       (non-empty strings, >= 1)
                  outcome      str     dispatched | rejected | handoff
                  slo_class    str     non-empty (interactive /
                                       standard / batch by default)
                  queue_depth  int     >= 0 at the decision
  outcome == "handoff" additionally:
                  from_engine  str     prefill engine (in fleet, and
                                       != engine — a self-handoff is
                                       a wiring bug)
                  pages_moved  int     paged/hybrid: >= 1 pages in
                                       the moved chain; recurrent:
                                       MUST be 0 (the chain is one
                                       fixed-size state blob, no
                                       pages cross)
                  chain_tokens int     >= 1 tokens the chain covers
                  page_size    int     >= 1; paged/hybrid counts must
                                       RECONCILE: pages_moved ==
                                       ceil(chain_tokens / page_size)
                                       (the chain covers exactly its
                                       written tokens — a mismatch
                                       means pages leaked or doubled
                                       across the handoff)
                  state_bytes  int     recurrent/hybrid: > 0 bytes of
                                       recurrent state riding the
                                       handoff (the whole payload for
                                       recurrent, the SSM half for
                                       hybrid)
                  and optionally:
                  prefix_affinity bool sticky prefix routing applied
                  prefix_match_pages int >= 0
                  deadline_ms  number  >= 0
                  router / request_id str non-empty
  kind == "kvcache" (periodic cache-pool snapshot —
                  pool_stats() via serve_observatory; the shape is
                  strategy-dispatched on cache_strategy)
                  cache_strategy == "recurrent" requires INSTEAD:
                  engine       str     emitting engine (non-empty)
                  n_slots      int     >= 1 state slots in the pool
                  free_slots   int     >= 0; free + held <= n_slots
                  held_slots   int     >= 0
                  sequences    int     >= 0 live sequences
                  slots_drawn  int     >= 0 cumulative slot draws
                  state_bytes  int     >= 1 fixed blob bytes per slot
                                       (the O(1) in O(1)-cache)
                  state_bytes_total int >= 0 whole-pool state bytes
                                       ... and every page gauge below
                                       must be ABSENT or ZERO (a
                                       recurrent pool has no pages)
                  cache_strategy "paged" (default) or "hybrid"
                  additionally requires:
                  engine       str     emitting engine (non-empty)
                  n_pages      int     pool size (>= 1)
                  free_pages   int     >= 0
                  held_pages   int     >= 0 pages with >= 1 holder;
                                       free + held <= n_pages (page 0
                                       is the reserved pad page)
                  shared_pages int     >= 0, <= held_pages
                  registered_pages int >= 0, <= held_pages (prefix
                                       registry holds)
                  pages_drawn  int     >= 0 cumulative pool draws
                  cow_copies   int     >= 0 cumulative copy-on-writes
                  lru_reclaims int     >= 0 cumulative registry evicts
                  and optionally:
                  evictable_pages int  >= 0, <= registered_pages
                  refcounts    dict    {refcount: n_pages >= 0}
                  page_size / prefix_nodes / sequences / queue_depth /
                  active       int     >= 0 (page_size >= 1)
                  hybrid additionally requires n_slots / free_slots /
                  held_slots / state_bytes / state_bytes_total (same
                  ranges as the recurrent snapshot; state_bytes > 0)
                  — the page pool and the slot pool report together
  kind == "journey" (ONE record per handed-off request at its
                  decode-side terminal — the fleet observatory,
                  profiler/fleet_observatory.py, joins the prefill and
                  decode request records) additionally requires:
                  request_id   str     non-empty; matches BOTH engine
                                       request records and the handoff
                                       route record
                  prefill_engine str   non-empty
                  decode_engine str    non-empty, != prefill_engine (a
                                       self-journey means the handoff
                                       never left the engine)
                  slo_class    str     interactive | standard | batch
                  outcome      str     completed | expired | error |
                                       cancelled (never rejected — a
                                       rejected request has no journey
                                       — and never handoff, which is
                                       not terminal)
                  prompt_tokens int    >= 0
                  generated_tokens int >= 0 (decode-side total,
                                       including the prefill engine's
                                       first streamed token)
                  pages_moved  int     same strategy-conditional rule
                                       as the handoff route record:
                                       paged/hybrid >= 1 and ==
                                       ceil(chain_tokens / page_size);
                                       recurrent == 0 (with
                                       state_bytes > 0 — one blob)
                  chain_tokens int     >= 1
                  page_size    int     >= 1
                  queue_s      number  >= 0 submit -> prefill admit
                  prefill_s    number  >= 0 admit -> chain export
                  handoff_gap_s number >= 0 chain export -> decode
                                       adoption (MEASURED at both ends,
                                       never inferred)
                  decode_s     number  >= 0 adoption -> terminal
                  latency_s    number  >= 0; >= the four phases' sum
                                       up to rounding (the boundaries
                                       telescope)
                  and optionally:
                  ttft_s       number  >= 0 submit -> the PREFILL
                                       engine's first streamed token
                  router       str     non-empty
                  deadline_s   number  >= 0
                  deadline_met bool    completed within deadline_s
                  proposed_tokens / accepted_tokens / accept_rate —
                                       same speculative trio as the
                                       request record (copied from the
                                       decode-side record; accepted <=
                                       generated_tokens)
  kind == "fleet" (periodic router-level fleet snapshot —
                  profiler/fleet_observatory.py FleetMonitor over
                  ServingRouter.load_report) additionally requires:
                  router       str     non-empty
                  fleet        list    engine names (non-empty strings)
                  n_engines    int     >= 1
                  n_pools      int     >= 1, <= n_engines (shared pools
                                       deduplicated)
                  queue_depth / active / slots_free int >= 0 (fleet
                                       totals)
                  admittable_pages / free_pages int >= 0
                  outstanding_claims int >= 0 admission claims over
                                       unique pools
                  saturated    list    subset of fleet
                  engines      dict    per-engine rollup; keys must be
                                       a subset of fleet; a member's
                                       optional accept_rate (the
                                       engine's cumulative speculative
                                       accept rate) must be in [0, 1]
                  window_s     number  >= 0 seconds since the previous
                                       snapshot (0 on the first)
                  arrival_rate / completion_rate / handoff_rate /
                  rejection_rate number >= 0 per-second over window_s
                                       (0 on the first snapshot)
                  slo_attainment dict  {class: fraction in [0, 1]}
                  requests / dispatched / rejected / handoffs int >= 0
                                       cumulative router counters
  kind == "harness" (ONE summary record per tools/load_harness.py
                  open-loop run) additionally requires:
                  router       str     non-empty
                  seed         int     the trace's RNG seed
                  requests     int     >= 1 requests in the trace
                  duration_s   number  >= 0 wall seconds of the run
                  goodput_tokens_per_s number >= 0 (deadline-met
                                       tokens only)
                  rejected_fraction / expired_fraction number in [0, 1]
                  peak_in_flight int   >= 0
                  ttft_p50_s / ttft_p99_s / tpot_p50_s / tpot_p99_s
                               number  >= 0 (p99 >= p50 up to rounding)
                  and optionally:
                  attainment_by_class dict {class: fraction in [0, 1]}
                  phases       dict    per-phase (before/burst/after)
                                       sub-summaries
  kind == "memory" (periodic device-memory attribution —
                  profiler/mem_observatory.py; emitted from the train
                  step cadence AND each serving engine's kvcache
                  cadence) additionally requires:
                  source       str     non-empty ("train" / "serve")
                  step         int     >= 0 emitting step counter
                  measured     bool    allocator stats answered (false
                                       = ledger-arithmetic fallback on
                                       statless backends)
                  tags         dict    {tag: bytes int >= 0} — the
                                       attribution ledger's per-tag
                                       view
                  attributed_bytes int >= 0, deduplicated over shared
                                       buffers; MUST be <=
                                       device_bytes_in_use (attribution
                                       cannot exceed what the device
                                       holds)
                  unattributed_bytes int >= 0 (in_use - attributed)
                  device_bytes_in_use int >= 0
                  device_peak_bytes int >= device_bytes_in_use is NOT
                                       required (peak is all-time) but
                                       must be >= 0
                  device_bytes_limit int >= 0 (0 = unknown)
                  executable_peak_bytes int >= 0 (compile ledger's
                                       temp/scratch bound)
                  and when a pool rides along (serve records),
                  strategy-conditional on cache_strategy (the PR 19
                  enum; absent = train-path record, no pool fields):
                  paged/hybrid require n_pages int >= 1, free_pages /
                  held_pages int >= 0, hbm_total_bytes /
                  hbm_free_bytes / hbm_headroom_bytes int >= 0
                  (headroom <= free <= total), page_bytes int >= 1;
                  optional fragmentation fields: fragmentation number
                  in [0, 1], free_runs / largest_free_run int >= 0
                  with largest_free_run <= free_pages,
                  free_run_histogram dict {bucket: count >= 1};
                  recurrent/hybrid require free_slots / held_slots /
                  state_bytes_total int >= 0

Extra keys are allowed (the schema is open for forward compat); missing
or mistyped required keys are violations.

A FILE whose content is a Chrome trace JSON (an object with a
"traceEvents" array — e.g. `Profiler.export_chrome_tracing(path)` or a
`tools/merge_traces.py` output) is validated as a trace instead:
strictly-parsing JSON (no bare NaN/Infinity tokens), every event a dict
with a `ph`, numeric `ts` (and `dur` for complete "X" events),
non-decreasing ts per (pid, tid) track, matched B/E begin/end pairs, and
matched s/f flow-arrow pairs per flow id (the routing track's handoff
arrows — a dangling start or finish is a broken join).

Usage: python tools/check_metrics_schema.py FILE [FILE...]
Exit 0 when every line of every file validates, 1 otherwise.
"""
import json
import math
import sys

BASE_REQUIRED = {"ts": (int, float), "rank": int, "kind": str}
STEP_REQUIRED = {"step": int, "step_time_s": (int, float),
                 "compile_s": (int, float), "cache_hit": bool,
                 "peak_bytes": int, "flops": (int, float),
                 "mfu": (int, float)}
SERVE_REQUIRED = {"engine": str, "requests": int, "batch_size": int,
                  "bucket_batch": int, "queue_depth": int,
                  "pad_tokens": int, "latency_s": (int, float)}
HEALTH_REQUIRED = {"step": int, "loss": (int, float, str),
                   "grad_norm": (int, float, str),
                   "param_norm": (int, float, str),
                   "update_ratio": (int, float, str),
                   "found_inf": (int, float, str)}
EVENT_REQUIRED = {"event": str}
COMPILE_REQUIRED = {"tag": str, "signature": str,
                    "lower_s": (int, float), "compile_s": (int, float),
                    "cache_hit": bool, "instructions": int,
                    "fusion_count": int, "bytes_accessed": (int, float),
                    "flops": (int, float),
                    "peak_memory_bytes": (int, float)}
WARM_REQUIRED = {"n_executables": int, "compiled_now": int,
                 "cache_hits": int, "wall_s": (int, float),
                 "sum_s": (int, float)}
SEED_REQUIRED = {"source": str, "cache_dir": str, "entries_seeded": int,
                 "entries_skipped": int}
LINT_REQUIRED = {"pass": str, "rule": str, "file": str, "line": int,
                 "severity": str, "message": str, "suppressed": bool}
# mirror of tools/lint/__init__.py KNOWN_PASS_NAMES (this tool stays a
# standalone no-import diff; tests/test_static_analysis.py asserts the
# two sets never drift)
LINT_PASSES = {"lock-order", "blocking-under-lock",
               "unlocked-shared-state", "use-after-donate", "hot-sync",
               "suppression"}
LINT_SEVERITIES = {"error", "warning"}
CKPT_REQUIRED = {"op": str, "step": int, "dir": str}
CKPT_OPS = {"save", "restore", "gc"}
CKPT_SAVE_REQUIRED = {"snapshot_s": (int, float),
                      "serialize_s": (int, float),
                      "write_s": (int, float), "commit_s": (int, float),
                      "total_s": (int, float), "bytes": int,
                      "n_leaves": int, "committed": bool}
CKPT_RESTORE_REQUIRED = {"verified": bool, "fell_back": int,
                         "bytes": int, "total_s": (int, float)}
CKPT_PHASES = ("snapshot_s", "serialize_s", "write_s", "commit_s")
REQUEST_REQUIRED = {"engine": str, "request_id": str, "outcome": str,
                    "rows": int, "prompt_tokens": int,
                    "prefix_hit_tokens": int, "generated_tokens": int,
                    "queue_s": (int, float), "latency_s": (int, float)}
REQUEST_OUTCOMES = {"completed", "expired", "rejected", "error",
                    "cancelled", "handoff"}
ROUTE_REQUIRED = {"engine": str, "fleet": list, "outcome": str,
                  "slo_class": str, "queue_depth": int}
ROUTE_OUTCOMES = {"dispatched", "rejected", "handoff"}
ROUTE_HANDOFF_REQUIRED = {"from_engine": str, "pages_moved": int,
                          "chain_tokens": int, "page_size": int}
JOURNEY_REQUIRED = {"request_id": str, "prefill_engine": str,
                    "decode_engine": str, "slo_class": str,
                    "outcome": str, "prompt_tokens": int,
                    "generated_tokens": int, "pages_moved": int,
                    "chain_tokens": int, "page_size": int,
                    "queue_s": (int, float), "prefill_s": (int, float),
                    "handoff_gap_s": (int, float),
                    "decode_s": (int, float),
                    "latency_s": (int, float)}
# terminal decode-side outcomes only: "rejected" dies before any
# handoff and "handoff" itself is never terminal
JOURNEY_OUTCOMES = {"completed", "expired", "error", "cancelled"}
SLO_CLASSES = {"interactive", "standard", "batch"}
# cache strategies (inference/cache_strategy.py): the optional
# `cache_strategy` stamp on serve/request/route/journey/kvcache
# records; absent means "paged" (pre-strategy records stay valid).
# Strategy-conditional rules: a RECURRENT chain moves ONE fixed-size
# state blob — pages_moved == 0 and state_bytes > 0 — while paged and
# hybrid chains move >= 1 page reconciling with chain_tokens.
CACHE_STRATEGIES = {"paged", "recurrent", "hybrid"}
# a recurrent pool snapshot counts STATE SLOTS, not pages: page
# gauges are absent (zero pages exist to count)
KVCACHE_RECURRENT_REQUIRED = {"engine": str, "n_slots": int,
                              "free_slots": int, "held_slots": int,
                              "sequences": int, "slots_drawn": int,
                              "state_bytes": int,
                              "state_bytes_total": int}
FLEET_REQUIRED = {"router": str, "fleet": list, "n_engines": int,
                  "n_pools": int, "queue_depth": int, "active": int,
                  "slots_free": int, "admittable_pages": int,
                  "free_pages": int, "outstanding_claims": int,
                  "saturated": list, "engines": dict,
                  "window_s": (int, float),
                  "arrival_rate": (int, float),
                  "completion_rate": (int, float),
                  "handoff_rate": (int, float),
                  "rejection_rate": (int, float),
                  "slo_attainment": dict, "requests": int,
                  "dispatched": int, "rejected": int, "handoffs": int}
HARNESS_REQUIRED = {"router": str, "seed": int, "requests": int,
                    "duration_s": (int, float),
                    "goodput_tokens_per_s": (int, float),
                    "rejected_fraction": (int, float),
                    "expired_fraction": (int, float),
                    "peak_in_flight": int,
                    "ttft_p50_s": (int, float),
                    "ttft_p99_s": (int, float),
                    "tpot_p50_s": (int, float),
                    "tpot_p99_s": (int, float)}
KVCACHE_REQUIRED = {"engine": str, "n_pages": int, "free_pages": int,
                    "held_pages": int, "shared_pages": int,
                    "registered_pages": int, "pages_drawn": int,
                    "cow_copies": int, "lru_reclaims": int}
COLLECTIVE_REQUIRED = {"op": str, "group": str, "bytes": int,
                       "wall_s": (int, float), "bw_gbps": (int, float)}
MEMORY_REQUIRED = {"source": str, "step": int, "measured": bool,
                   "tags": dict, "attributed_bytes": int,
                   "unattributed_bytes": int,
                   "device_bytes_in_use": int,
                   "device_peak_bytes": int, "device_bytes_limit": int,
                   "executable_peak_bytes": int}
# pool fields a serve-path memory record carries, by strategy (the
# train path carries none — no cache rides its cadence)
MEMORY_PAGED_REQUIRED = {"n_pages": int, "free_pages": int,
                         "held_pages": int, "hbm_total_bytes": int,
                         "hbm_free_bytes": int,
                         "hbm_headroom_bytes": int, "page_bytes": int}
MEMORY_RECURRENT_REQUIRED = {"free_slots": int, "held_slots": int,
                             "state_bytes_total": int}
RANKSTAT_REQUIRED = {"step": int, "world_size": int,
                     "step_time_p50_s": (int, float),
                     "step_time_p99_s": (int, float),
                     "host_blocked_s": (int, float),
                     "collective_wait_s": (int, float),
                     "collective_wait_share": (int, float),
                     "peak_bytes": int}
# a persistent-cache HIT deserializes an artifact instead of compiling;
# spending more than this on one is a mislabeled cold compile
CACHE_HIT_COMPILE_S_MAX = 10.0
# repr strings a non-finite health scalar may export as
_NONFINITE_STRS = {"nan", "inf", "-inf"}


def _int_val(rec, key):
    """rec[key] as an int (bools excluded), else None."""
    v = rec.get(key)
    return v if isinstance(v, int) and not isinstance(v, bool) else None


def _num_val(rec, key):
    """rec[key] as a number (bools excluded), else None."""
    v = rec.get(key)
    return v if isinstance(v, (int, float)) and not isinstance(v, bool) \
        else None


def _cache_strategy(rec, where, errors):
    """Validate the optional cache_strategy enum; return its effective
    value ("paged" when absent — pre-strategy records stay valid)."""
    if "cache_strategy" not in rec:
        return "paged"
    v = rec["cache_strategy"]
    if not isinstance(v, str) or v not in CACHE_STRATEGIES:
        errors.append(
            f"{where}: cache_strategy {v!r} not one of "
            f"{sorted(CACHE_STRATEGIES)}")
        return "paged"
    return v


def _check_chain_moved(rec, where, errors, strategy, what):
    """Strategy-conditional handoff-payload rules shared by route
    (outcome handoff) and journey records: what crossed engines must
    reconcile with the strategy's currency."""
    moved = _int_val(rec, "pages_moved")
    toks = _int_val(rec, "chain_tokens")
    psize = _int_val(rec, "page_size")
    sbytes = _int_val(rec, "state_bytes") if "state_bytes" in rec \
        else None
    if "state_bytes" in rec and sbytes is None:
        errors.append(
            f"{where}: state_bytes must be an int, got "
            f"{rec['state_bytes']!r}")
    for key, v in (("chain_tokens", toks), ("page_size", psize)):
        if v is not None and v < 1:
            errors.append(f"{where}: {key} must be >= 1, got {v}")
    if strategy == "recurrent":
        if moved is not None and moved != 0:
            errors.append(
                f"{where}: recurrent {what} moved pages_moved {moved} "
                "— a recurrent chain is ONE state blob, it moves no "
                "pages")
        if sbytes is not None and sbytes <= 0:
            errors.append(
                f"{where}: recurrent {what} with state_bytes "
                f"{sbytes} — the state blob is the payload, its size "
                "must be > 0")
        return
    if moved is not None and moved < 1:
        errors.append(
            f"{where}: pages_moved must be >= 1, got {moved}")
    if None not in (moved, toks, psize) and psize >= 1 and \
            moved != -(-toks // psize):
        errors.append(
            f"{where}: pages_moved {moved} != ceil(chain_tokens "
            f"{toks} / page_size {psize}) — the {what}'s page count "
            "does not reconcile with the tokens it claims to carry")
    if strategy == "hybrid" and sbytes is not None and sbytes <= 0:
        errors.append(
            f"{where}: hybrid {what} with state_bytes {sbytes} — the "
            "recurrent half's blob must ride the handoff too")


def _check_types(rec, required, where, errors):
    for key, types in required.items():
        if key not in rec:
            errors.append(f"{where}: missing required key {key!r}")
            continue
        val = rec[key]
        # bool is an int subclass: only cache_hit may be bool
        if isinstance(val, bool) and types is not bool:
            errors.append(f"{where}: key {key!r} is bool, expected "
                          f"{types}")
        elif not isinstance(val, types):
            errors.append(f"{where}: key {key!r} has type "
                          f"{type(val).__name__}, expected {types}")


def _check_spec_fields(rec, where, errors):
    """The speculative-decoding trio (optional on serve, request, and
    journey records — inference/speculative.py): proposed_tokens /
    accepted_tokens int >= 0 with accepted <= proposed (a verify step
    can never accept drafts nobody proposed), accept_rate a number in
    [0, 1] that reconciles with the counts — exactly accepted/proposed
    when anything was proposed, and EXACTLY zero on a non-speculative
    record (nonspec engines must stamp zeros, not omit arithmetic)."""
    prop = rec.get("proposed_tokens")
    acc = rec.get("accepted_tokens")
    rate = rec.get("accept_rate")

    def _i(v):
        return v if isinstance(v, int) and not isinstance(v, bool) \
            else None

    for key, v in (("proposed_tokens", prop), ("accepted_tokens", acc)):
        if key in rec and (_i(v) is None or v < 0):
            errors.append(
                f"{where}: {key} must be an int >= 0, got {v!r}")
    if _i(prop) is not None and _i(acc) is not None and acc > prop:
        errors.append(
            f"{where}: accepted_tokens {acc} > proposed_tokens {prop} "
            "— acceptance cannot outrun the draft")
    if "accept_rate" in rec:
        if not isinstance(rate, (int, float)) or isinstance(rate, bool) \
                or not 0.0 <= rate <= 1.0:
            errors.append(
                f"{where}: accept_rate must be a number in [0, 1], "
                f"got {rate!r}")
        elif _i(prop) is not None and _i(acc) is not None:
            want = (acc / prop) if prop else 0.0
            if abs(rate - want) > 1e-6:
                errors.append(
                    f"{where}: accept_rate {rate} does not reconcile "
                    f"with accepted/proposed = {want:.6f} — the ratio "
                    "and the counters must be the same measurement")


def validate_line(line, where="<line>"):
    """Errors (list of strings, empty = valid) for one JSONL line."""
    errors = []
    try:
        rec = json.loads(line)
    except ValueError as e:
        return [f"{where}: not valid JSON ({e})"]
    if not isinstance(rec, dict):
        return [f"{where}: not a JSON object"]
    _check_types(rec, BASE_REQUIRED, where, errors)
    if rec.get("kind") == "step":
        _check_types(rec, STEP_REQUIRED, where, errors)
        if isinstance(rec.get("step"), int) and \
                not isinstance(rec.get("step"), bool) and rec["step"] < 1:
            errors.append(f"{where}: step must be >= 1, got {rec['step']}")
        # fused-epilogue cost split (optional, typed+ranged when present)
        if "epilogue_bytes" in rec:
            v = rec["epilogue_bytes"]
            if not isinstance(v, int) or isinstance(v, bool) or v <= 0:
                errors.append(
                    f"{where}: epilogue_bytes must be an int > 0, "
                    f"got {v!r}")
        if "epilogue_share" in rec:
            v = rec["epilogue_share"]
            if not isinstance(v, (int, float)) or isinstance(v, bool) \
                    or not (0.0 <= v <= 1.0):
                errors.append(
                    f"{where}: epilogue_share must be a number in "
                    f"[0, 1], got {v!r}")
    elif rec.get("kind") == "serve":
        _check_types(rec, SERVE_REQUIRED, where, errors)
        _cache_strategy(rec, where, errors)
        # engine is REQUIRED and non-empty: it is the only key that
        # keeps multi-engine JSONL attributable
        if isinstance(rec.get("engine"), str) and not rec["engine"]:
            errors.append(
                f"{where}: engine must be a non-empty string, "
                f"got {rec['engine']!r}")

        def _ok_int(key):
            v = rec.get(key)
            return isinstance(v, int) and not isinstance(v, bool)

        for key, lo in (("requests", 1), ("batch_size", 1),
                        ("pad_tokens", 0), ("queue_depth", 0)):
            if _ok_int(key) and rec[key] < lo:
                errors.append(
                    f"{where}: {key} must be >= {lo}, got {rec[key]}")
        lat = rec.get("latency_s")
        if isinstance(lat, (int, float)) and not isinstance(lat, bool) \
                and lat < 0:
            errors.append(
                f"{where}: latency_s must be >= 0, got {lat} (negative "
                "latency means a clock/accounting bug upstream)")
        if _ok_int("bucket_batch") and _ok_int("batch_size") and \
                rec["bucket_batch"] < rec["batch_size"]:
            errors.append(
                f"{where}: bucket_batch {rec['bucket_batch']} < "
                f"batch_size {rec['batch_size']} — the bucket must fit "
                "the rows it padded")
        # ragged-serving fields (optional, typed+ranged when present)
        for key in ("prefix_hits", "shared_pages",
                    "chunked_prefill_tokens", "tokens", "bucket_tokens"):
            if key in rec:
                v = rec[key]
                if not isinstance(v, int) or isinstance(v, bool) \
                        or v < 0:
                    errors.append(
                        f"{where}: {key} must be an int >= 0, got {v!r}")
        if "pad_token_fraction" in rec:
            v = rec["pad_token_fraction"]
            if not isinstance(v, (int, float)) or isinstance(v, bool) \
                    or not (0.0 <= v <= 1.0):
                errors.append(
                    f"{where}: pad_token_fraction must be a number in "
                    f"[0, 1], got {v!r}")
        _check_spec_fields(rec, where, errors)
    elif rec.get("kind") == "health":
        _check_types(rec, HEALTH_REQUIRED, where, errors)
        if isinstance(rec.get("step"), int) and \
                not isinstance(rec.get("step"), bool) and rec["step"] < 1:
            errors.append(f"{where}: step must be >= 1, got {rec['step']}")
        for key in ("grad_norm", "param_norm", "update_ratio",
                    "found_inf"):
            v = rec.get(key)
            if isinstance(v, str):
                if v.lower() not in _NONFINITE_STRS:
                    errors.append(
                        f"{where}: {key} string must be a non-finite "
                        f"repr ({sorted(_NONFINITE_STRS)}), got {v!r}")
            elif isinstance(v, (int, float)) and \
                    not isinstance(v, bool) and v < 0:
                errors.append(
                    f"{where}: {key} must be >= 0, got {v}")
        fi = rec.get("found_inf")
        if isinstance(fi, (int, float)) and not isinstance(fi, bool) \
                and fi not in (0, 1):
            errors.append(
                f"{where}: found_inf must be 0 or 1, got {fi}")
    elif rec.get("kind") == "event":
        _check_types(rec, EVENT_REQUIRED, where, errors)
        if isinstance(rec.get("event"), str) and not rec["event"]:
            errors.append(f"{where}: event name must be non-empty")
    elif rec.get("kind") == "compile":
        _check_types(rec, COMPILE_REQUIRED, where, errors)
        for key in ("tag", "signature"):
            if isinstance(rec.get(key), str) and not rec[key]:
                errors.append(f"{where}: {key} must be non-empty")

        def _num(key):
            v = rec.get(key)
            return v if isinstance(v, (int, float)) and \
                not isinstance(v, bool) else None

        for key in ("lower_s", "compile_s", "bytes_accessed", "flops",
                    "peak_memory_bytes", "instructions", "fusion_count"):
            v = _num(key)
            if v is not None and v < 0:
                errors.append(f"{where}: {key} must be >= 0, got {v}")
        comp = _num("compile_s")
        if rec.get("cache_hit") is True and comp is not None and \
                comp > CACHE_HIT_COMPILE_S_MAX:
            errors.append(
                f"{where}: cache_hit record spent {comp}s in compile_s "
                f"(> {CACHE_HIT_COMPILE_S_MAX}s) — a hit loads an "
                "artifact, it does not compile")
        for key in ("op_counts", "kernels"):
            ops = rec.get(key)
            if ops is None:
                continue
            if not isinstance(ops, dict):
                errors.append(f"{where}: {key} must be a dict, got "
                              f"{type(ops).__name__}")
                continue
            for k, v in ops.items():
                if not isinstance(k, str) or not isinstance(v, int) \
                        or isinstance(v, bool) or v < 0:
                    errors.append(
                        f"{where}: {key} entry {k!r}: {v!r} must "
                        "be str -> int >= 0")
                    break
    elif rec.get("kind") == "warm":
        _check_types(rec, WARM_REQUIRED, where, errors)

        def _int(key):
            v = rec.get(key)
            return v if isinstance(v, int) and not isinstance(v, bool) \
                else None

        for key in ("n_executables", "compiled_now", "cache_hits"):
            v = _int(key)
            if v is not None and v < 0:
                errors.append(f"{where}: {key} must be >= 0, got {v}")
        for key in ("wall_s", "sum_s"):
            v = rec.get(key)
            if isinstance(v, (int, float)) and not isinstance(v, bool) \
                    and v < 0:
                errors.append(f"{where}: {key} must be >= 0, got {v}")
        n, c, h = _int("n_executables"), _int("compiled_now"), \
            _int("cache_hits")
        if n is not None and c is not None and c > n:
            errors.append(
                f"{where}: compiled_now {c} > n_executables {n} — a "
                "warm set cannot compile more than it holds")
        if c is not None and h is not None and h > c:
            errors.append(
                f"{where}: cache_hits {h} > compiled_now {c} — only a "
                "compile that ran can be a cache load")
        tags = rec.get("tags")
        if tags is not None:
            if not isinstance(tags, list) or any(
                    not isinstance(t, str) or not t for t in tags):
                errors.append(f"{where}: tags must be a list of "
                              f"non-empty strings, got {tags!r}")
    elif rec.get("kind") == "request":
        _check_types(rec, REQUEST_REQUIRED, where, errors)
        _cache_strategy(rec, where, errors)

        def _rint(key):
            return _int_val(rec, key)

        def _rnum(key):
            return _num_val(rec, key)

        for key in ("engine", "request_id"):
            if isinstance(rec.get(key), str) and not rec[key]:
                errors.append(f"{where}: {key} must be non-empty")
        outcome = rec.get("outcome")
        if isinstance(outcome, str) and outcome not in REQUEST_OUTCOMES:
            errors.append(
                f"{where}: outcome {outcome!r} not one of "
                f"{sorted(REQUEST_OUTCOMES)}")
        if _rint("rows") is not None and rec["rows"] < 1:
            errors.append(f"{where}: rows must be >= 1, got "
                          f"{rec['rows']}")
        for key in ("prompt_tokens", "prefix_hit_tokens",
                    "generated_tokens", "prefill_chunks",
                    "peak_pages_held"):
            v = _rint(key) if key in rec else None
            if v is not None and v < 0:
                errors.append(f"{where}: {key} must be >= 0, got {v}")
        for key in ("queue_s", "prefill_s", "decode_s", "latency_s",
                    "deadline_s", "ttft_s"):
            v = _rnum(key) if key in rec else None
            if v is not None and v < 0:
                errors.append(f"{where}: {key} must be >= 0, got {v}")
        for key in ("slo_class", "handoff_of"):
            if key in rec and (not isinstance(rec[key], str)
                               or not rec[key]):
                errors.append(
                    f"{where}: {key} must be a non-empty string, got "
                    f"{rec[key]!r}")
        if outcome == "handoff" and "handoff_of" not in rec:
            errors.append(
                f"{where}: outcome 'handoff' without handoff_of — the "
                "prefill half of a disaggregated pair must name its "
                "decode engine or the journey join is impossible")
        # cross-field: token counts must be consistent with the outcome
        hit, prompt = _rint("prefix_hit_tokens"), _rint("prompt_tokens")
        if hit is not None and prompt is not None and hit > prompt:
            errors.append(
                f"{where}: prefix_hit_tokens {hit} > prompt_tokens "
                f"{prompt} — the cache cannot serve tokens the prompt "
                "does not have")
        gen = _rint("generated_tokens")
        if gen is not None and outcome in ("rejected", "expired") \
                and gen != 0:
            errors.append(
                f"{where}: outcome {outcome!r} with generated_tokens "
                f"{gen} — a request that died before admission cannot "
                "have decoded")
        mx = _rint("max_new_tokens") if "max_new_tokens" in rec else None
        if mx is not None:
            if mx < 1:
                errors.append(
                    f"{where}: max_new_tokens must be >= 1, got {mx}")
            elif gen is not None and gen > mx:
                errors.append(
                    f"{where}: generated_tokens {gen} > max_new_tokens "
                    f"{mx}")
        lat = _rnum("latency_s")
        phases = [_rnum(k) for k in ("queue_s", "prefill_s", "decode_s")
                  if k in rec]
        if lat is not None and all(p is not None for p in phases) and \
                sum(phases) > lat + 1e-3:
            errors.append(
                f"{where}: phase seconds {sum(phases):.6f} exceed "
                f"latency_s {lat} — the lifecycle clock math is broken")
        if "deadline_met" in rec and not isinstance(
                rec["deadline_met"], bool):
            errors.append(
                f"{where}: deadline_met must be bool, got "
                f"{rec['deadline_met']!r}")
        _check_spec_fields(rec, where, errors)
        # cross-field: a request cannot accept more speculated tokens
        # than it generated (every accepted token IS an emitted token)
        sacc, sgen = _rint("accepted_tokens") \
            if "accepted_tokens" in rec else None, gen
        if sacc is not None and sgen is not None and sacc > sgen:
            errors.append(
                f"{where}: accepted_tokens {sacc} > generated_tokens "
                f"{sgen} — accepted speculative tokens are a subset of "
                "the generated stream")
    elif rec.get("kind") == "route":
        _check_types(rec, ROUTE_REQUIRED, where, errors)
        for key in ("engine", "slo_class"):
            if isinstance(rec.get(key), str) and not rec[key]:
                errors.append(f"{where}: {key} must be non-empty")
        fleet = rec.get("fleet")
        if isinstance(fleet, list):
            if not fleet or any(not isinstance(n, str) or not n
                                for n in fleet):
                errors.append(
                    f"{where}: fleet must be a non-empty list of "
                    f"non-empty engine names, got {fleet!r}")
            elif isinstance(rec.get("engine"), str) and rec["engine"] \
                    and rec["engine"] not in fleet:
                errors.append(
                    f"{where}: engine {rec['engine']!r} not in fleet "
                    f"{fleet} — the router placed work on an engine "
                    "it does not know about")
        outcome = rec.get("outcome")
        if isinstance(outcome, str) and outcome not in ROUTE_OUTCOMES:
            errors.append(
                f"{where}: route outcome {outcome!r} not one of "
                f"{sorted(ROUTE_OUTCOMES)}")
        qd = _int_val(rec, "queue_depth")
        if qd is not None and qd < 0:
            errors.append(
                f"{where}: queue_depth must be >= 0, got {qd}")
        strategy = _cache_strategy(rec, where, errors)
        if outcome == "handoff":
            _check_types(rec, ROUTE_HANDOFF_REQUIRED, where, errors)
            fe = rec.get("from_engine")
            if isinstance(fe, str):
                if not fe:
                    errors.append(f"{where}: from_engine must be "
                                  "non-empty")
                elif isinstance(fleet, list) and fleet and \
                        fe not in fleet:
                    errors.append(
                        f"{where}: from_engine {fe!r} not in fleet "
                        f"{fleet}")
                elif fe == rec.get("engine"):
                    errors.append(
                        f"{where}: handoff from {fe!r} to itself — "
                        "a self-handoff is a role-wiring bug")
            _check_chain_moved(rec, where, errors, strategy, "handoff")
        if "prefix_affinity" in rec and \
                not isinstance(rec["prefix_affinity"], bool):
            errors.append(
                f"{where}: prefix_affinity must be bool, got "
                f"{rec['prefix_affinity']!r}")
        pmp = _int_val(rec, "prefix_match_pages") \
            if "prefix_match_pages" in rec else None
        if pmp is not None and pmp < 0:
            errors.append(
                f"{where}: prefix_match_pages must be >= 0, got {pmp}")
        if "deadline_ms" in rec:
            v = _num_val(rec, "deadline_ms")
            if v is None or v < 0:
                errors.append(
                    f"{where}: deadline_ms must be a number >= 0, got "
                    f"{rec['deadline_ms']!r}")
        for key in ("router", "request_id"):
            if key in rec and (not isinstance(rec[key], str)
                               or not rec[key]):
                errors.append(
                    f"{where}: {key} must be a non-empty string, got "
                    f"{rec[key]!r}")
    elif rec.get("kind") == "journey":
        _check_types(rec, JOURNEY_REQUIRED, where, errors)
        for key in ("request_id", "prefill_engine", "decode_engine"):
            if isinstance(rec.get(key), str) and not rec[key]:
                errors.append(f"{where}: {key} must be non-empty")
        pe, de = rec.get("prefill_engine"), rec.get("decode_engine")
        if isinstance(pe, str) and isinstance(de, str) and pe \
                and pe == de:
            errors.append(
                f"{where}: prefill_engine == decode_engine ({pe!r}) — "
                "a journey exists BECAUSE the request crossed engines")
        cls = rec.get("slo_class")
        if isinstance(cls, str) and cls not in SLO_CLASSES:
            errors.append(
                f"{where}: slo_class {cls!r} not one of "
                f"{sorted(SLO_CLASSES)}")
        outcome = rec.get("outcome")
        if isinstance(outcome, str) and outcome not in JOURNEY_OUTCOMES:
            errors.append(
                f"{where}: journey outcome {outcome!r} not one of "
                f"{sorted(JOURNEY_OUTCOMES)} — rejected requests have "
                "no journey and 'handoff' is not terminal")
        for key in ("prompt_tokens", "generated_tokens"):
            v = _int_val(rec, key)
            if v is not None and v < 0:
                errors.append(f"{where}: {key} must be >= 0, got {v}")
        strategy = _cache_strategy(rec, where, errors)
        _check_chain_moved(rec, where, errors, strategy, "journey")
        for key in ("queue_s", "prefill_s", "handoff_gap_s", "decode_s",
                    "latency_s", "ttft_s", "deadline_s"):
            v = _num_val(rec, key) if key in rec else None
            if v is not None and v < 0:
                errors.append(f"{where}: {key} must be >= 0, got {v}")
        lat = _num_val(rec, "latency_s")
        phases = [_num_val(rec, k) for k in
                  ("queue_s", "prefill_s", "handoff_gap_s", "decode_s")]
        if lat is not None and all(p is not None for p in phases) and \
                sum(phases) > lat + 1e-3:
            errors.append(
                f"{where}: phase seconds {sum(phases):.6f} exceed "
                f"latency_s {lat} — the journey's boundary stamps must "
                "telescope")
        _check_spec_fields(rec, where, errors)
        jacc = _int_val(rec, "accepted_tokens") \
            if "accepted_tokens" in rec else None
        jgen = _int_val(rec, "generated_tokens")
        if jacc is not None and jgen is not None and jacc > jgen:
            errors.append(
                f"{where}: accepted_tokens {jacc} > generated_tokens "
                f"{jgen} — the journey's speculative accounting must "
                "reconcile with its decode record")
        if "deadline_met" in rec and not isinstance(
                rec["deadline_met"], bool):
            errors.append(
                f"{where}: deadline_met must be bool, got "
                f"{rec['deadline_met']!r}")
        if "router" in rec and (not isinstance(rec["router"], str)
                                or not rec["router"]):
            errors.append(
                f"{where}: router must be a non-empty string, got "
                f"{rec['router']!r}")
    elif rec.get("kind") == "fleet":
        _check_types(rec, FLEET_REQUIRED, where, errors)
        if isinstance(rec.get("router"), str) and not rec["router"]:
            errors.append(f"{where}: router must be non-empty")
        fleet = rec.get("fleet")
        fleet_ok = isinstance(fleet, list) and fleet and \
            all(isinstance(n, str) and n for n in fleet)
        if isinstance(fleet, list) and not fleet_ok:
            errors.append(
                f"{where}: fleet must be a non-empty list of non-empty "
                f"engine names, got {fleet!r}")
        for key in ("n_engines", "n_pools"):
            v = _int_val(rec, key)
            if v is not None and v < 1:
                errors.append(f"{where}: {key} must be >= 1, got {v}")
        ne, np_ = _int_val(rec, "n_engines"), _int_val(rec, "n_pools")
        if None not in (ne, np_) and np_ > ne:
            errors.append(
                f"{where}: n_pools {np_} > n_engines {ne} — pools are "
                "shared across engines, never multiplied")
        for key in ("queue_depth", "active", "slots_free",
                    "admittable_pages", "free_pages",
                    "outstanding_claims", "requests", "dispatched",
                    "rejected", "handoffs", "hbm_total_bytes",
                    "hbm_free_bytes", "hbm_headroom_bytes"):
            v = _int_val(rec, key)
            if v is not None and v < 0:
                errors.append(f"{where}: {key} must be >= 0, got {v}")
        # measured-bytes rollup ordering: headroom subtracts claims
        # from free, free is a subset of total — inverted gauges mean
        # the per-pool dedup or the pool arithmetic broke
        ht = _int_val(rec, "hbm_total_bytes")
        hf = _int_val(rec, "hbm_free_bytes")
        hh = _int_val(rec, "hbm_headroom_bytes")
        if None not in (hf, ht) and hf > ht:
            errors.append(
                f"{where}: hbm_free_bytes {hf} > hbm_total_bytes {ht}")
        if None not in (hh, hf) and hh > hf:
            errors.append(
                f"{where}: hbm_headroom_bytes {hh} > hbm_free_bytes "
                f"{hf}")
        for key in ("window_s", "arrival_rate", "completion_rate",
                    "handoff_rate", "rejection_rate"):
            v = _num_val(rec, key)
            if v is not None and (v < 0 or math.isinf(v)
                                  or math.isnan(v)):
                errors.append(
                    f"{where}: {key} must be finite and >= 0, got {v}")
        if fleet_ok:
            sat = rec.get("saturated")
            if isinstance(sat, list):
                extra = [n for n in sat if n not in fleet]
                if extra:
                    errors.append(
                        f"{where}: saturated engines {extra} not in "
                        f"fleet {fleet}")
            engines = rec.get("engines")
            if isinstance(engines, dict):
                extra = [n for n in engines if n not in fleet]
                if extra:
                    errors.append(
                        f"{where}: engines keys {extra} not in fleet "
                        f"{fleet} — the rollup reports engines the "
                        "router does not own")
                for n, eng_rec in engines.items():
                    if isinstance(eng_rec, dict) and \
                            "accept_rate" in eng_rec:
                        v = eng_rec["accept_rate"]
                        if not isinstance(v, (int, float)) or \
                                isinstance(v, bool) or not 0 <= v <= 1:
                            errors.append(
                                f"{where}: engines[{n!r}].accept_rate "
                                f"must be in [0, 1], got {v!r}")
        attain = rec.get("slo_attainment")
        if isinstance(attain, dict):
            for cls, v in attain.items():
                if not isinstance(v, (int, float)) \
                        or isinstance(v, bool) or not 0 <= v <= 1:
                    errors.append(
                        f"{where}: slo_attainment[{cls!r}] must be in "
                        f"[0, 1], got {v!r}")
    elif rec.get("kind") == "harness":
        _check_types(rec, HARNESS_REQUIRED, where, errors)
        if isinstance(rec.get("router"), str) and not rec["router"]:
            errors.append(f"{where}: router must be non-empty")
        v = _int_val(rec, "requests")
        if v is not None and v < 1:
            errors.append(f"{where}: requests must be >= 1, got {v}")
        v = _int_val(rec, "peak_in_flight")
        if v is not None and v < 0:
            errors.append(
                f"{where}: peak_in_flight must be >= 0, got {v}")
        for key in ("duration_s", "goodput_tokens_per_s", "ttft_p50_s",
                    "ttft_p99_s", "tpot_p50_s", "tpot_p99_s"):
            v = _num_val(rec, key)
            if v is not None and v < 0:
                errors.append(f"{where}: {key} must be >= 0, got {v}")
        for key in ("rejected_fraction", "expired_fraction"):
            v = _num_val(rec, key)
            if v is not None and not 0 <= v <= 1:
                errors.append(
                    f"{where}: {key} must be in [0, 1], got {v}")
        for lo, hi in (("ttft_p50_s", "ttft_p99_s"),
                       ("tpot_p50_s", "tpot_p99_s")):
            a, b = _num_val(rec, lo), _num_val(rec, hi)
            if None not in (a, b) and b + 1e-9 < a:
                errors.append(
                    f"{where}: {hi} {b} < {lo} {a} — percentiles must "
                    "be ordered")
        if "attainment_by_class" in rec:
            abc = rec["attainment_by_class"]
            if not isinstance(abc, dict):
                errors.append(
                    f"{where}: attainment_by_class must be a dict, got "
                    f"{type(abc).__name__}")
            else:
                for cls, v in abc.items():
                    if not isinstance(v, (int, float)) \
                            or isinstance(v, bool) or not 0 <= v <= 1:
                        errors.append(
                            f"{where}: attainment_by_class[{cls!r}] "
                            f"must be in [0, 1], got {v!r}")
    elif rec.get("kind") == "kvcache":
        strategy = _cache_strategy(rec, where, errors)

        def _kint(key):
            return _int_val(rec, key)

        if isinstance(rec.get("engine"), str) and not rec["engine"]:
            errors.append(f"{where}: engine must be non-empty")
        if strategy == "recurrent":
            _check_types(rec, KVCACHE_RECURRENT_REQUIRED, where,
                         errors)
            if _kint("n_slots") is not None and rec["n_slots"] < 1:
                errors.append(
                    f"{where}: n_slots must be >= 1, got "
                    f"{rec['n_slots']}")
            for key in ("free_slots", "held_slots", "sequences",
                        "slots_drawn", "state_bytes_total"):
                v = _kint(key) if key in rec else None
                if v is not None and v < 0:
                    errors.append(
                        f"{where}: {key} must be >= 0, got {v}")
            sb = _kint("state_bytes")
            if sb is not None and sb < 1:
                errors.append(
                    f"{where}: state_bytes must be >= 1, got {sb} — "
                    "a recurrent slot's fixed blob size is the pool's "
                    "whole capacity story")
            ns, fs, hs = _kint("n_slots"), _kint("free_slots"), \
                _kint("held_slots")
            if None not in (ns, fs, hs) and fs + hs > ns:
                errors.append(
                    f"{where}: free_slots {fs} + held_slots {hs} > "
                    f"n_slots {ns} — slots are being double-counted")
            for key in ("n_pages", "free_pages", "held_pages",
                        "shared_pages", "registered_pages",
                        "pages_drawn", "cow_copies", "lru_reclaims"):
                v = _kint(key) if key in rec else None
                if v is not None and v != 0:
                    errors.append(
                        f"{where}: recurrent snapshot reports {key} "
                        f"{v} — a recurrent pool has no pages; page "
                        "gauges must be absent or zero")
            return errors
        _check_types(rec, KVCACHE_REQUIRED, where, errors)
        if strategy == "hybrid":
            for key in ("n_slots", "free_slots", "held_slots",
                        "state_bytes", "state_bytes_total"):
                if key not in rec:
                    errors.append(
                        f"{where}: hybrid snapshot missing {key} — "
                        "the recurrent half's slots must be reported "
                        "alongside the page pool")
                else:
                    v = _kint(key)
                    if v is None:
                        errors.append(
                            f"{where}: {key} must be an int, got "
                            f"{rec[key]!r}")
                    elif v < 0:
                        errors.append(
                            f"{where}: {key} must be >= 0, got {v}")
            sb = _kint("state_bytes")
            if sb is not None and sb == 0:
                errors.append(
                    f"{where}: hybrid snapshot with state_bytes 0 — "
                    "the recurrent half holds real state per slot")
        if _kint("n_pages") is not None and rec["n_pages"] < 1:
            errors.append(
                f"{where}: n_pages must be >= 1, got {rec['n_pages']}")
        for key in ("free_pages", "held_pages", "shared_pages",
                    "registered_pages", "pages_drawn", "cow_copies",
                    "lru_reclaims", "evictable_pages", "page_size",
                    "prefix_nodes", "sequences", "queue_depth",
                    "active"):
            v = _kint(key) if key in rec else None
            if v is not None and v < 0:
                errors.append(f"{where}: {key} must be >= 0, got {v}")
        n, free, held = _kint("n_pages"), _kint("free_pages"), \
            _kint("held_pages")
        if n is not None and free is not None and held is not None \
                and free + held > n:
            errors.append(
                f"{where}: free_pages {free} + held_pages {held} > "
                f"n_pages {n} — pages are being double-counted")
        for key in ("shared_pages", "registered_pages"):
            v = _kint(key)
            if v is not None and held is not None and v > held:
                errors.append(
                    f"{where}: {key} {v} > held_pages {held}")
        ev = _kint("evictable_pages") if "evictable_pages" in rec \
            else None
        reg = _kint("registered_pages")
        if ev is not None and reg is not None and ev > reg:
            errors.append(
                f"{where}: evictable_pages {ev} > registered_pages "
                f"{reg} — only registry-held pages are evictable")
        rc = rec.get("refcounts")
        if rc is not None:
            if not isinstance(rc, dict):
                errors.append(f"{where}: refcounts must be a dict, got "
                              f"{type(rc).__name__}")
            else:
                for k, v in rc.items():
                    if not isinstance(k, str) or not isinstance(v, int) \
                            or isinstance(v, bool) or v < 0:
                        errors.append(
                            f"{where}: refcounts entry {k!r}: {v!r} "
                            "must be str -> int >= 0")
                        break
    elif rec.get("kind") == "collective":
        _check_types(rec, COLLECTIVE_REQUIRED, where, errors)
        for key in ("op", "group"):
            if isinstance(rec.get(key), str) and not rec[key]:
                errors.append(f"{where}: {key} must be non-empty")
        b = _int_val(rec, "bytes")
        if b is not None and b < 0:
            errors.append(f"{where}: bytes must be >= 0, got {b}")
        w = _num_val(rec, "wall_s")
        if w is not None and w < 0:
            errors.append(f"{where}: wall_s must be >= 0, got {w}")
        bw = _num_val(rec, "bw_gbps")
        if bw is not None:
            if not math.isfinite(bw):
                errors.append(
                    f"{where}: bw_gbps must be FINITE, got {bw!r} — an "
                    "infinite bandwidth means the zero-time guard "
                    "upstream broke")
            elif bw < 0:
                errors.append(f"{where}: bw_gbps must be >= 0, got {bw}")
        if "traced" in rec and not isinstance(rec["traced"], bool):
            errors.append(f"{where}: traced must be bool, got "
                          f"{rec['traced']!r}")
        c = _int_val(rec, "calls") if "calls" in rec else None
        if c is not None and c < 1:
            errors.append(f"{where}: calls must be >= 1, got {c}")
    elif rec.get("kind") == "rankstat":
        _check_types(rec, RANKSTAT_REQUIRED, where, errors)
        step = _int_val(rec, "step")
        if step is not None and step < 0:
            errors.append(f"{where}: step must be >= 0, got {step}")
        world = _int_val(rec, "world_size")
        if world is not None and world < 1:
            errors.append(
                f"{where}: world_size must be >= 1, got {world}")
        # cross-field: the emitting rank must exist in the world
        rk = _int_val(rec, "rank")
        if rk is not None and world is not None and rk >= world:
            errors.append(
                f"{where}: rank {rk} >= world_size {world} — a rank "
                "outside the world means the launch env lies")
        for key in ("step_time_p50_s", "step_time_p99_s",
                    "host_blocked_s", "collective_wait_s"):
            v = _num_val(rec, key)
            if v is not None and v < 0:
                errors.append(f"{where}: {key} must be >= 0, got {v}")
        p50, p99 = _num_val(rec, "step_time_p50_s"), \
            _num_val(rec, "step_time_p99_s")
        if p50 is not None and p99 is not None and p99 < p50 - 1e-9:
            errors.append(
                f"{where}: step_time_p99_s {p99} < step_time_p50_s "
                f"{p50} — percentiles cannot invert")
        share = _num_val(rec, "collective_wait_share")
        if share is not None and not (0.0 <= share <= 1.0):
            errors.append(
                f"{where}: collective_wait_share must be in [0, 1], "
                f"got {share} — the share is capped by the step time "
                "it is measured against")
        pb = _int_val(rec, "peak_bytes")
        if pb is not None and pb < 0:
            errors.append(f"{where}: peak_bytes must be >= 0, got {pb}")
        so = _int_val(rec, "steps_observed") \
            if "steps_observed" in rec else None
        if so is not None and so < 0:
            errors.append(
                f"{where}: steps_observed must be >= 0, got {so}")
        if "clock_offset_s" in rec:
            v = rec["clock_offset_s"]
            if not isinstance(v, (int, float)) or isinstance(v, bool) \
                    or not math.isfinite(v):
                errors.append(
                    f"{where}: clock_offset_s must be a finite number, "
                    f"got {v!r}")
    elif rec.get("kind") == "ckpt":
        _check_types(rec, CKPT_REQUIRED, where, errors)
        op = rec.get("op")
        if isinstance(op, str) and op not in CKPT_OPS:
            errors.append(f"{where}: ckpt op {op!r} not one of "
                          f"{sorted(CKPT_OPS)}")
        if isinstance(rec.get("dir"), str) and not rec["dir"]:
            errors.append(f"{where}: dir must be non-empty")
        step = _int_val(rec, "step")
        if step is not None and step < 0:
            errors.append(f"{where}: step must be >= 0, got {step}")
        if op == "save":
            _check_types(rec, CKPT_SAVE_REQUIRED, where, errors)
            for key in CKPT_PHASES + ("total_s",):
                v = _num_val(rec, key)
                if v is not None and v < 0:
                    errors.append(f"{where}: {key} must be >= 0, got {v}")
            phases = [_num_val(rec, k) for k in CKPT_PHASES]
            total = _num_val(rec, "total_s")
            if total is not None and all(p is not None for p in phases) \
                    and sum(phases) > total + 1e-3:
                errors.append(
                    f"{where}: ckpt phase seconds {sum(phases):.6f} "
                    f"exceed total_s {total} — the phases run inside "
                    "the save's wall window, the clock math is broken")
            b = _int_val(rec, "bytes")
            n = _int_val(rec, "n_leaves")
            if rec.get("committed") is True:
                if b is not None and b <= 0:
                    errors.append(
                        f"{where}: committed save with bytes {b} — an "
                        "empty committed checkpoint is a lie")
                if n is not None and n < 1:
                    errors.append(
                        f"{where}: committed save with n_leaves {n}")
            elif b is not None and b < 0:
                errors.append(f"{where}: bytes must be >= 0, got {b}")
        elif op == "restore":
            _check_types(rec, CKPT_RESTORE_REQUIRED, where, errors)
            for key, lo in (("fell_back", 0), ("bytes", 0)):
                v = _int_val(rec, key)
                if v is not None and v < lo:
                    errors.append(
                        f"{where}: {key} must be >= {lo}, got {v}")
            v = _num_val(rec, "total_s")
            if v is not None and v < 0:
                errors.append(f"{where}: total_s must be >= 0, got {v}")
        elif op == "gc":
            v = _int_val(rec, "removed")
            if v is None:
                errors.append(f"{where}: gc record missing int "
                              "'removed'")
            elif v < 1:
                errors.append(
                    f"{where}: gc record with removed {v} — a GC that "
                    "deleted nothing must not emit a record")
    elif rec.get("kind") == "lint":
        _check_types(rec, LINT_REQUIRED, where, errors)
        p = rec.get("pass")
        if isinstance(p, str) and p not in LINT_PASSES:
            errors.append(f"{where}: lint pass {p!r} not one of "
                          f"{sorted(LINT_PASSES)}")
        for key in ("rule", "file", "message"):
            if isinstance(rec.get(key), str) and not rec[key]:
                errors.append(f"{where}: {key} must be non-empty")
        ln = _int_val(rec, "line")
        if ln is not None and ln < 0:
            errors.append(f"{where}: line must be >= 0, got {ln}")
        sev = rec.get("severity")
        if isinstance(sev, str) and sev not in LINT_SEVERITIES:
            errors.append(f"{where}: severity {sev!r} not one of "
                          f"{sorted(LINT_SEVERITIES)}")
        if rec.get("suppressed") is True:
            r = rec.get("reason")
            if not isinstance(r, str) or not r.strip():
                errors.append(
                    f"{where}: suppressed lint finding with no reason "
                    "— a suppression must say WHY (got "
                    f"{r!r})")
    elif rec.get("kind") == "seed":
        _check_types(rec, SEED_REQUIRED, where, errors)
        for key in ("source", "cache_dir"):
            if isinstance(rec.get(key), str) and not rec[key]:
                errors.append(f"{where}: {key} must be non-empty")
        for key in ("entries_seeded", "entries_skipped"):
            v = rec.get(key)
            if isinstance(v, int) and not isinstance(v, bool) and v < 0:
                errors.append(f"{where}: {key} must be >= 0, got {v}")
    elif rec.get("kind") == "memory":
        _check_types(rec, MEMORY_REQUIRED, where, errors)
        if isinstance(rec.get("source"), str) and not rec["source"]:
            errors.append(f"{where}: source must be non-empty")
        tags = rec.get("tags")
        if isinstance(tags, dict):
            for tag, v in tags.items():
                if not isinstance(v, int) or isinstance(v, bool) \
                        or v < 0:
                    errors.append(
                        f"{where}: tags[{tag!r}] must be an int >= 0, "
                        f"got {v!r}")
        for key in ("step", "attributed_bytes", "unattributed_bytes",
                    "device_bytes_in_use", "device_peak_bytes",
                    "device_bytes_limit", "executable_peak_bytes"):
            v = _int_val(rec, key)
            if v is not None and v < 0:
                errors.append(f"{where}: {key} must be >= 0, got {v}")
        # THE attribution bound: the deduplicated ledger total can
        # never exceed what the device reports in use (on statless
        # backends the fallback pins in_use to the ledger, so the
        # bound holds in both modes)
        att = _int_val(rec, "attributed_bytes")
        use = _int_val(rec, "device_bytes_in_use")
        if None not in (att, use) and att > use:
            errors.append(
                f"{where}: attributed_bytes {att} > "
                f"device_bytes_in_use {use} — attribution cannot "
                "exceed what the device holds")
        # pool fields ride only on serve-path records (cache_strategy
        # present); strategy-conditional like the kvcache branch
        if "cache_strategy" in rec:
            strategy = _cache_strategy(rec, where, errors)
            if isinstance(rec.get("engine"), str) and not rec["engine"]:
                errors.append(f"{where}: engine must be non-empty")
            if strategy in ("paged", "hybrid"):
                _check_types(rec, MEMORY_PAGED_REQUIRED, where, errors)
                np_ = _int_val(rec, "n_pages")
                if np_ is not None and np_ < 1:
                    errors.append(
                        f"{where}: n_pages must be >= 1, got {np_}")
                pb = _int_val(rec, "page_bytes")
                if pb is not None and pb < 1:
                    errors.append(
                        f"{where}: page_bytes must be >= 1, got {pb}")
                for key in ("free_pages", "held_pages"):
                    v = _int_val(rec, key)
                    if v is not None and v < 0:
                        errors.append(
                            f"{where}: {key} must be >= 0, got {v}")
                ht = _int_val(rec, "hbm_total_bytes")
                hf = _int_val(rec, "hbm_free_bytes")
                hh = _int_val(rec, "hbm_headroom_bytes")
                for key, v in (("hbm_total_bytes", ht),
                               ("hbm_free_bytes", hf),
                               ("hbm_headroom_bytes", hh)):
                    if v is not None and v < 0:
                        errors.append(
                            f"{where}: {key} must be >= 0, got {v}")
                if None not in (hf, ht) and hf > ht:
                    errors.append(
                        f"{where}: hbm_free_bytes {hf} > "
                        f"hbm_total_bytes {ht}")
                if None not in (hh, hf) and hh > hf:
                    errors.append(
                        f"{where}: hbm_headroom_bytes {hh} > "
                        f"hbm_free_bytes {hf}")
            if strategy in ("recurrent", "hybrid"):
                _check_types(rec, MEMORY_RECURRENT_REQUIRED, where,
                             errors)
                for key in ("free_slots", "held_slots",
                            "state_bytes_total"):
                    v = _int_val(rec, key)
                    if v is not None and v < 0:
                        errors.append(
                            f"{where}: {key} must be >= 0, got {v}")
        # fragmentation is MEASURED from the free list: the metric is
        # a fraction, the largest run can never exceed the free count
        frag = _num_val(rec, "fragmentation")
        if frag is not None and not 0 <= frag <= 1:
            errors.append(
                f"{where}: fragmentation must be in [0, 1], got "
                f"{frag}")
        for key in ("free_runs", "largest_free_run"):
            v = _int_val(rec, key)
            if v is not None and v < 0:
                errors.append(f"{where}: {key} must be >= 0, got {v}")
        lr = _int_val(rec, "largest_free_run")
        fp = _int_val(rec, "free_pages")
        if None not in (lr, fp) and lr > fp:
            errors.append(
                f"{where}: largest_free_run {lr} > free_pages {fp} — "
                "a contiguous run is a subset of the free list")
        hist = rec.get("free_run_histogram")
        if hist is not None:
            if not isinstance(hist, dict):
                errors.append(
                    f"{where}: free_run_histogram must be a dict, got "
                    f"{type(hist).__name__}")
            else:
                for bucket, n in hist.items():
                    if not isinstance(n, int) or isinstance(n, bool) \
                            or n < 1:
                        errors.append(
                            f"{where}: free_run_histogram[{bucket!r}] "
                            f"must be an int >= 1, got {n!r}")
    return errors


def _strict_loads(text):
    """json.loads that REJECTS bare NaN/Infinity tokens — Perfetto's
    JSON parser does, so the lint must too."""
    def bad_constant(name):
        raise ValueError(f"non-JSON constant {name}")
    return json.loads(text, parse_constant=bad_constant)


def validate_trace(path, text=None):
    """Violations for one Chrome-trace-event JSON file (the object
    format {"traceEvents": [...]} or the bare array format)."""
    errors = []
    if text is None:
        with open(path) as f:
            text = f.read()
    try:
        payload = _strict_loads(text)
    except ValueError as e:
        return [f"{path}: not strict JSON ({e})"]
    events = payload.get("traceEvents") if isinstance(payload, dict) \
        else payload
    if not isinstance(events, list):
        return [f"{path}: no traceEvents array"]
    if not events:
        return [f"{path}: empty trace (no events)"]
    last_ts = {}     # (pid, tid) -> last non-meta ts
    open_b = {}      # (pid, tid) -> count of unmatched B events
    flow_s = {}      # flow id -> count of "s" starts
    flow_f = {}      # flow id -> count of "f" finishes
    for i, e in enumerate(events):
        where = f"{path}: event {i}"
        if not isinstance(e, dict):
            errors.append(f"{where}: not an object")
            continue
        ph = e.get("ph")
        if not isinstance(ph, str) or not ph:
            errors.append(f"{where}: missing ph")
            continue
        ts = e.get("ts")
        if not isinstance(ts, (int, float)) or isinstance(ts, bool):
            errors.append(f"{where}: ph={ph} missing numeric ts")
            continue
        key = (e.get("pid", 0), e.get("tid", 0))
        if ph == "M":
            continue  # metadata carries ts 0, outside the track order
        if ph == "X":
            dur = e.get("dur")
            if not isinstance(dur, (int, float)) or isinstance(dur, bool) \
                    or dur < 0:
                errors.append(f"{where}: X event needs dur >= 0, "
                              f"got {dur!r}")
        elif ph == "B":
            open_b[key] = open_b.get(key, 0) + 1
        elif ph == "E":
            if open_b.get(key, 0) <= 0:
                errors.append(f"{where}: E without matching B on "
                              f"track {key}")
            else:
                open_b[key] -= 1
        elif ph in ("s", "t", "f"):
            fid = e.get("id")
            if fid is None:
                errors.append(f"{where}: flow event ph={ph!r} "
                              "missing id")
            elif ph == "s":
                flow_s[fid] = flow_s.get(fid, 0) + 1
            elif ph == "f":
                flow_f[fid] = flow_f.get(fid, 0) + 1
        if key in last_ts and ts < last_ts[key]:
            errors.append(
                f"{where}: ts {ts} < previous {last_ts[key]} on track "
                f"{key} — events must be sorted per track")
        last_ts[key] = ts
    for key, n in open_b.items():
        if n:
            errors.append(f"{path}: {n} unmatched B event(s) on track "
                          f"{key}")
    # flow arrows pair per id: a dangling start never lands and a
    # dangling finish came from nowhere — both mean a broken join
    for fid in sorted(set(flow_s) | set(flow_f), key=str):
        ns, nf = flow_s.get(fid, 0), flow_f.get(fid, 0)
        if ns != nf:
            errors.append(
                f"{path}: flow id {fid!r} has {ns} start(s) but {nf} "
                "finish(es) — s/f arrows must pair")
    return errors


def validate_file(path):
    """All violations in one file; ["<path>: empty file"] when empty.
    A file whose whole content is a JSON object with a traceEvents
    array (or a bare event array) validates as a Chrome trace; anything
    else validates line-by-line as metrics JSONL."""
    errors = []
    with open(path) as f:
        text = f.read()
    stripped = text.lstrip()
    if stripped.startswith(("{", "[")):
        try:
            payload = _strict_loads(text)
        except ValueError:
            payload = None
        if isinstance(payload, list) or (
                isinstance(payload, dict) and "traceEvents" in payload):
            return validate_trace(path, text=text)
    lines = text.splitlines()
    if not any(line.strip() for line in lines):
        return [f"{path}: empty file (no records emitted)"]
    last_save_step = {}  # rank -> last committed ckpt save step
    for lineno, line in enumerate(lines, 1):
        if not line.strip():
            continue
        where = f"{path}:{lineno}"
        errors.extend(validate_line(line, where))
        # cross-line: committed checkpoint save steps must be
        # non-decreasing per rank (a backwards step counter means the
        # process resumed from the wrong checkpoint)
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if isinstance(rec, dict) and rec.get("kind") == "ckpt" and \
                rec.get("op") == "save" and rec.get("committed") is True:
            step = _int_val(rec, "step")
            rank = rec.get("rank")
            if step is not None:
                prev = last_save_step.get(rank)
                if prev is not None and step < prev:
                    errors.append(
                        f"{where}: ckpt save step {step} < previous "
                        f"committed save step {prev} for rank {rank} — "
                        "the step counter must be monotonic")
                last_save_step[rank] = step
    return errors


def main(argv):
    if not argv:
        print(__doc__.strip().splitlines()[-2].strip())
        return 2
    all_errors = []
    for path in argv:
        all_errors.extend(validate_file(path))
    for err in all_errors:
        print(err)
    if all_errors:
        print(f"FAIL: {len(all_errors)} schema violation(s)")
        return 1
    print(f"OK: {len(argv)} file(s) validate")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
