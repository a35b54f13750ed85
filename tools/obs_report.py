#!/usr/bin/env python
"""obs_report — the one-command answer to "what happened in this run".

Renders a human summary from a paddle_tpu metrics JSONL file (the
PADDLE_TPU_METRICS_FILE export — docs/OBSERVABILITY.md): training step
rollup with every kind:"stall" record under it (what covered the long
step interval), the compile
ledger per executable, the serving SLO/goodput rollup, the front-door
routing section (per-engine placements, handoffs, fleet SLO), the
cross-engine journey section (kind:"journey" phase splits + the
journey-vs-request-pair token reconciliation), the fleet snapshot /
load-harness section, the device-memory ledger section (kind:"memory"
per-tag peaks + attribution MISMATCH lines), the
distributed
observatory's collective top-k by wall time and per-rank skew table,
every anomaly event (stragglers, spikes, retraces, NaNs) in order, and
the static-analysis findings section (kind:"lint" — paddlelint).

Plain json + arithmetic — no framework import, so it runs anywhere the
JSONL landed (a laptop holding a pulled rank log included).

Usage: python tools/obs_report.py METRICS.jsonl [--top N]
Exit 0 on a rendered report, 2 on unreadable input.
"""
import argparse
import json
import sys


def load_records(path):
    recs = []
    with open(path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue  # a torn tail line must not kill the report
            if isinstance(rec, dict):
                recs.append(rec)
    return recs


def _pct(sorted_vals, p):
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1,
              max(0, int(round(p / 100.0 * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


def _fmt_s(v):
    if v >= 1.0:
        return f"{v:.2f}s"
    return f"{v * 1e3:.2f}ms"


def section_steps(recs, out):
    steps = [r for r in recs if r.get("kind") == "step"]
    scans = [r for r in recs if r.get("kind") == "scan"]
    if not steps and not scans:
        return
    out.append("== training ==")
    if steps:
        times = sorted(float(r.get("step_time_s", 0.0)) for r in steps)
        compile_s = sum(float(r.get("compile_s", 0.0)) for r in steps)
        mfus = [float(r.get("mfu", 0.0)) for r in steps
                if r.get("mfu", 0.0)]
        out.append(
            f"  {len(steps)} steps  wall {sum(times):.2f}s  "
            f"p50 {_fmt_s(_pct(times, 50))}  p99 {_fmt_s(_pct(times, 99))}"
            f"  compile {compile_s:.2f}s")
        if mfus:
            out.append(f"  mfu (cost analysis, last): {mfus[-1]:.4f}")
    if scans:
        n = sum(int(r.get("steps", 0)) for r in scans)
        out.append(f"  {len(scans)} scanned segments ({n} steps)")
    for r in recs:
        if r.get("kind") == "stall":
            out.extend(_stall_lines(r))
    out.append("")


def _stall_lines(r):
    """A kind:"stall" record under its step: the interval against the
    median, the collector's and the CPU's seconds in it, what no span
    on the step's thread covered, and the spans that covered most."""
    def top(spans):
        return ", ".join(f"{k} {_fmt_s(float(v))}" for k, v in sorted(
            spans.items(), key=lambda kv: -kv[1])[:4]) or "nothing"
    cov = r.get("covered", {})
    return [f"  stall at step {r.get('step')}: "
            f"{_fmt_s(float(r.get('interval_s', 0.0)))} against a median "
            f"of {_fmt_s(float(r.get('median_s', 0.0)))}  gc "
            f"{_fmt_s(float(r.get('gc_s', 0.0)))}  cpu "
            f"{_fmt_s(float(r.get('cpu_s', 0.0)))}  uncovered "
            f"{_fmt_s(float(r.get('uncovered_s', 0.0)))}",
            f"    step thread: {top(cov.get('step_thread', {}))}",
            f"    other threads: {top(cov.get('other_threads', {}))}"]


def section_compiles(recs, out, top):
    comps = [r for r in recs if r.get("kind") == "compile"]
    if not comps:
        return
    by_tag = {}
    for r in comps:
        t = by_tag.setdefault(r.get("tag", "?"),
                              {"n": 0, "s": 0.0, "hits": 0, "kernels": {},
                               "kept": {}})
        t["n"] += 1
        t["kernels"] = r.get("kernels") or t["kernels"]  # the newest
        t["kept"] = r.get("remat_saved_bytes") or t["kept"]
        t["s"] += float(r.get("lower_s", 0.0)) + \
            float(r.get("compile_s", 0.0))
        t["hits"] += 1 if r.get("cache_hit") else 0
    out.append(f"== compiles ==  ({len(comps)} records, "
               f"{sum(t['s'] for t in by_tag.values()):.2f}s total)")
    rows = sorted(by_tag.items(), key=lambda kv: -kv[1]["s"])[:top]
    for tag, t in rows:
        out.append(f"  {tag:<28} {t['s']:>8.2f}s  "
                   f"x{t['n']}  cache hits {t['hits']}/{t['n']}")
        if t["kernels"]:    # Pallas kernels under their scope: which
            out.append("    kernels: " + "  ".join(     # layout flash got
                f"{k} x{v}" for k, v in sorted(t["kernels"].items())))
        if t["kept"]:       # what the backward pass reads, by save point
            out.append("    kept for backward: " + "  ".join(
                f"{k} {v / 2**20:.1f} MiB"
                for k, v in sorted(t["kept"].items())))
    out.append("")


def section_serve(recs, out):
    reqs = [r for r in recs if r.get("kind") == "request"]
    if not reqs:
        return
    outcomes = {}
    for r in reqs:
        outcomes[r.get("outcome", "?")] = \
            outcomes.get(r.get("outcome", "?"), 0) + 1
    # a "handoff" record is the NON-terminal prefill half of a
    # disaggregated pair — its tokens are re-counted by the decode-side
    # record (seeded at adoption), so it stays out of the token math
    gen = sum(int(r.get("generated_tokens", 0)) for r in reqs
              if r.get("outcome") != "handoff")
    good = sum(int(r.get("generated_tokens", 0)) for r in reqs
               if r.get("outcome") == "completed")
    dl = [r for r in reqs if "deadline_met" in r]
    met = sum(1 for r in dl if r.get("deadline_met"))
    lats = sorted(float(r.get("latency_s", 0.0)) for r in reqs)
    out.append(f"== serving ==  ({len(reqs)} requests)")
    out.append("  outcomes: " + "  ".join(
        f"{k}={v}" for k, v in sorted(outcomes.items())))
    # cache strategy split: legacy records predate the field and are
    # paged by construction, so an all-paged ledger stays as before
    by_strat = {}
    for r in reqs:
        s = r.get("cache_strategy", "paged")
        t = by_strat.setdefault(s, {"n": 0, "engines": set()})
        t["n"] += 1
        t["engines"].add(r.get("engine", "?"))
    if set(by_strat) != {"paged"}:
        out.append("  cache strategies: " + "  ".join(
            f"{s}={t['n']} ({len(t['engines'])} engine"
            f"{'s' if len(t['engines']) != 1 else ''})"
            for s, t in sorted(by_strat.items())))
    out.append(f"  latency p50 {_fmt_s(_pct(lats, 50))}  "
               f"p99 {_fmt_s(_pct(lats, 99))}")
    waste = gen - good
    out.append(f"  tokens: goodput {good}  wasted {waste}")
    if dl:
        out.append(f"  slo attainment: {met}/{len(dl)} "
                   f"({met / len(dl):.3f})")
    out.append("")


def section_routing(recs, out):
    """The serving front door (kind:"route" — ServingRouter,
    paddle_tpu/inference/frontdoor.py): per-engine placement counts by
    SLO class, prefill->decode handoffs with the pages they moved,
    rejections, and the fleet SLO rollup joined from the request
    ledger (deadline attainment per engine)."""
    routes = [r for r in recs if r.get("kind") == "route"]
    if not routes:
        return
    disp = [r for r in routes if r.get("outcome") == "dispatched"]
    hoffs = [r for r in routes if r.get("outcome") == "handoff"]
    rej = [r for r in routes if r.get("outcome") == "rejected"]
    out.append(f"== routing ==  ({len(routes)} decisions: "
               f"{len(disp)} dispatched, {len(hoffs)} handoffs, "
               f"{len(rej)} rejected)")
    by_engine = {}
    for r in disp:
        e = by_engine.setdefault(r.get("engine", "?"),
                                 {"n": 0, "cls": {}, "aff": 0})
        e["n"] += 1
        cls = r.get("slo_class", "?")
        e["cls"][cls] = e["cls"].get(cls, 0) + 1
        e["aff"] += 1 if r.get("prefix_affinity") else 0
    for name in sorted(by_engine):
        e = by_engine[name]
        cls_txt = "  ".join(f"{k}={v}" for k, v in sorted(
            e["cls"].items()))
        out.append(f"  {name:<24} {e['n']:>4} placed  [{cls_txt}]"
                   f"  prefix-affinity {e['aff']}")
    if hoffs:
        pairs = {}
        for r in hoffs:
            key = (r.get("from_engine", "?"), r.get("engine", "?"))
            p = pairs.setdefault(key, {"n": 0, "pages": 0, "toks": 0,
                                       "sbytes": 0})
            p["n"] += 1
            p["pages"] += int(r.get("pages_moved", 0))
            p["toks"] += int(r.get("chain_tokens", 0))
            p["sbytes"] += int(r.get("state_bytes", 0))
        for (src, dst), p in sorted(pairs.items()):
            # a recurrent handoff moves zero pages — its payload is the
            # fixed-size state blob, so show the bytes when they exist
            sb = f"  {p['sbytes']} state bytes" if p["sbytes"] else ""
            out.append(f"  handoff {src} -> {dst}: x{p['n']}  "
                       f"{p['pages']} pages  {p['toks']} kv tokens{sb}")
    # fleet SLO rollup: join the request ledger per placed engine
    reqs = [r for r in recs if r.get("kind") == "request"
            and "deadline_met" in r]
    if reqs:
        by_eng = {}
        for r in reqs:
            b = by_eng.setdefault(r.get("engine", "?"), [0, 0])
            b[0] += 1 if r.get("deadline_met") else 0
            b[1] += 1
        met = sum(b[0] for b in by_eng.values())
        total = sum(b[1] for b in by_eng.values())
        per = "  ".join(f"{k}={b[0]}/{b[1]}"
                        for k, b in sorted(by_eng.items()))
        out.append(f"  fleet slo: {met}/{total} "
                   f"({met / total:.3f})  [{per}]")
    out.append("")


def section_journeys(recs, out):
    """Cross-engine request journeys (kind:"journey" — the fleet
    observatory, profiler/fleet_observatory.py): the phase split of
    every handed-off request, per prefill->decode pair, plus the
    reconciliation of each journey against its TWO request records
    (joined on request_id, cross-named by handoff_of) — a pair whose
    token counts disagree means the adoption seeding lied."""
    js = [r for r in recs if r.get("kind") == "journey"]
    if not js:
        return
    gaps = sorted(float(r.get("handoff_gap_s", 0.0)) for r in js)
    lats = sorted(float(r.get("latency_s", 0.0)) for r in js)
    out.append(f"== journeys ==  ({len(js)} handed-off requests)")
    out.append(f"  latency p50 {_fmt_s(_pct(lats, 50))}  "
               f"p99 {_fmt_s(_pct(lats, 99))}  handoff gap p50 "
               f"{_fmt_s(_pct(gaps, 50))}  p99 {_fmt_s(_pct(gaps, 99))}")
    for key in ("queue_s", "prefill_s", "handoff_gap_s", "decode_s"):
        vals = sorted(float(r.get(key, 0.0)) for r in js)
        out.append(f"  {key:<14} p50 {_fmt_s(_pct(vals, 50))}")
    pairs = {}
    for r in js:
        key = (r.get("prefill_engine", "?"), r.get("decode_engine", "?"))
        p = pairs.setdefault(key, {"n": 0, "pages": 0, "met": 0,
                                   "dl": 0})
        p["n"] += 1
        p["pages"] += int(r.get("pages_moved", 0))
        if "deadline_met" in r:
            p["dl"] += 1
            p["met"] += 1 if r.get("deadline_met") else 0
    for (src, dst), p in sorted(pairs.items()):
        slo = f"  slo {p['met']}/{p['dl']}" if p["dl"] else ""
        out.append(f"  {src} -> {dst}: x{p['n']}  "
                   f"{p['pages']} pages{slo}")
    # pair reconciliation: journey vs its two request records
    by_rid = {}
    for r in recs:
        if r.get("kind") == "request" and r.get("request_id"):
            by_rid.setdefault(r["request_id"], []).append(r)
    ok, bad = 0, []
    for j in js:
        rid = j.get("request_id")
        sides = by_rid.get(rid, [])
        pre = [r for r in sides if r.get("outcome") == "handoff"
               and r.get("engine") == j.get("prefill_engine")]
        dec = [r for r in sides if r.get("outcome") != "handoff"
               and r.get("engine") == j.get("decode_engine")]
        if len(pre) != 1 or len(dec) != 1:
            bad.append(f"{rid}: {len(pre)} prefill / {len(dec)} decode "
                       "record(s), expected 1+1")
            continue
        p, d = pre[0], dec[0]
        pgen = int(p.get("generated_tokens", 0))
        dgen = int(d.get("generated_tokens", 0))
        if p.get("handoff_of") != j.get("decode_engine") or \
                d.get("handoff_of") != j.get("prefill_engine"):
            bad.append(f"{rid}: handoff_of cross-naming broken "
                       f"({p.get('handoff_of')!r} / "
                       f"{d.get('handoff_of')!r})")
        elif dgen < pgen or dgen != int(j.get("generated_tokens", 0)):
            bad.append(
                f"{rid}: tokens do not reconcile (prefill {pgen}, "
                f"decode {dgen}, journey "
                f"{j.get('generated_tokens')}) — the decode side is "
                "seeded with the prefill tokens and must carry the "
                "journey total")
        else:
            ok += 1
    out.append(f"  pair reconciliation: {ok}/{len(js)} journeys "
               "match their request-record pairs")
    for msg in bad[:5]:
        out.append(f"  MISMATCH {msg}")
    out.append("")


def section_fleet(recs, out):
    """Fleet snapshots (kind:"fleet") + load-harness summaries
    (kind:"harness"): the latest per-router snapshot's load and rates,
    and each harness run's goodput/SLO line."""
    fleets = [r for r in recs if r.get("kind") == "fleet"]
    harness = [r for r in recs if r.get("kind") == "harness"]
    if not fleets and not harness:
        return
    out.append(f"== fleet ==  ({len(fleets)} snapshot(s), "
               f"{len(harness)} harness run(s))")
    latest = {}
    for r in fleets:
        latest[r.get("router", "?")] = r  # file order: last wins
    for name in sorted(latest):
        r = latest[name]
        sat = r.get("saturated") or []
        sat_txt = f"  SATURATED {sat}" if sat else ""
        out.append(
            f"  {name}: {r.get('n_engines', '?')} engines / "
            f"{r.get('n_pools', '?')} pool(s)  queue "
            f"{r.get('queue_depth', 0)}  active {r.get('active', 0)}  "
            f"claims {r.get('outstanding_claims', 0)}{sat_txt}")
        out.append(
            f"    rates/s: in {r.get('arrival_rate', 0)}  done "
            f"{r.get('completion_rate', 0)}  handoff "
            f"{r.get('handoff_rate', 0)}  reject "
            f"{r.get('rejection_rate', 0)}")
        att = r.get("slo_attainment") or {}
        if att:
            out.append("    slo attainment: " + "  ".join(
                f"{k}={v:.3f}" for k, v in sorted(att.items())))
    for r in harness:
        out.append(
            f"  harness seed={r.get('seed', '?')} "
            f"{r.get('requests', '?')} reqs in "
            f"{float(r.get('duration_s', 0.0)):.1f}s: goodput "
            f"{float(r.get('goodput_tokens_per_s', 0.0)):.1f} tok/s  "
            f"ttft p50 {_fmt_s(float(r.get('ttft_p50_s', 0.0)))} "
            f"p99 {_fmt_s(float(r.get('ttft_p99_s', 0.0)))}  rejected "
            f"{float(r.get('rejected_fraction', 0.0)):.3f}  expired "
            f"{float(r.get('expired_fraction', 0.0)):.3f}  peak "
            f"in-flight {r.get('peak_in_flight', '?')}")
    out.append("")


def section_collectives(recs, out, top):
    colls = [r for r in recs if r.get("kind") == "collective"]
    if not colls:
        return
    by_op = {}
    for r in colls:
        t = by_op.setdefault(r.get("op", "?"),
                             {"n": 0, "s": 0.0, "b": 0, "bw": []})
        t["n"] += 1
        t["s"] += float(r.get("wall_s", 0.0))
        t["b"] += int(r.get("bytes", 0))
        bw = float(r.get("bw_gbps", 0.0))
        if bw > 0:
            t["bw"].append(bw)
    out.append(f"== collectives ==  ({len(colls)} sampled records; "
               f"top {top} by sampled wall time)")
    rows = sorted(by_op.items(), key=lambda kv: -kv[1]["s"])[:top]
    for op, t in rows:
        bw = sorted(t["bw"])
        bw_txt = f"  bw p50 {_pct(bw, 50):.2f} GB/s" if bw else ""
        out.append(f"  {op:<16} {t['s'] * 1e3:>9.3f}ms sampled  "
                   f"x{t['n']}  {t['b']} bytes{bw_txt}")
    out.append("")


def section_ranks(recs, out):
    rstats = [r for r in recs if r.get("kind") == "rankstat"]
    if not rstats:
        return
    latest = {}
    for r in rstats:
        latest[r.get("rank", 0)] = r  # file order: last wins
    out.append(f"== ranks ==  ({len(rstats)} rankstat records, "
               f"{len(latest)} rank(s))")
    for rank in sorted(latest):
        r = latest[rank]
        out.append(
            f"  rank {rank}: step p50 "
            f"{_fmt_s(float(r.get('step_time_p50_s', 0.0)))}  "
            f"p99 {_fmt_s(float(r.get('step_time_p99_s', 0.0)))}  "
            f"coll wait {float(r.get('collective_wait_share', 0.0)):.3f}"
            f"  blocked {_fmt_s(float(r.get('host_blocked_s', 0.0)))}  "
            f"clock {float(r.get('clock_offset_s', 0.0)) * 1e3:+.1f}ms")
    out.append("")


def _fmt_bytes(v):
    v = float(v)
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(v) < 1024.0 or unit == "GiB":
            return f"{v:.1f}{unit}" if unit != "B" else f"{int(v)}B"
        v /= 1024.0


def section_memory(recs, out):
    """Device-memory ledger rollup (kind:"memory" —
    profiler/mem_observatory.py): per-tag peak bytes across the run's
    records, the last record's attribution split, and a MISMATCH line
    whenever a measured record's unattributed bytes exceed what the
    compile ledger's executable peaks can explain — the leak signature
    the memory observatory exists to surface."""
    mems = [r for r in recs if r.get("kind") == "memory"]
    if not mems:
        return
    sources = {}
    for r in mems:
        sources[r.get("source", "?")] = sources.get(
            r.get("source", "?"), 0) + 1
    out.append(f"== memory ==  ({len(mems)} records: " + "  ".join(
        f"{k}={v}" for k, v in sorted(sources.items())) + ")")
    peaks = {}
    for r in mems:
        for tag, b in (r.get("tags") or {}).items():
            if isinstance(b, (int, float)) and not isinstance(b, bool):
                peaks[tag] = max(peaks.get(tag, 0), int(b))
    for tag, b in sorted(peaks.items(), key=lambda kv: -kv[1]):
        out.append(f"  {tag:<28} peak {_fmt_bytes(b):>10}")
    last = mems[-1]
    out.append(
        f"  last: attributed {_fmt_bytes(last.get('attributed_bytes', 0))}"
        f"  unattributed {_fmt_bytes(last.get('unattributed_bytes', 0))}"
        f"  in_use {_fmt_bytes(last.get('device_bytes_in_use', 0))}"
        f"  measured={bool(last.get('measured'))}")
    frags = [float(r.get("fragmentation", 0.0)) for r in mems
             if "fragmentation" in r]
    if frags:
        out.append(f"  kv fragmentation: last {frags[-1]:.3f}  "
                   f"max {max(frags):.3f}")
    # a measured record whose unattributed bytes exceed the compile
    # ledger's executable peaks (plus 10%-of-device or 1 MiB slack)
    # points at memory NO tag or executable explains
    for r in mems:
        if not r.get("measured"):
            continue
        unattr = int(r.get("unattributed_bytes", 0))
        bound = int(r.get("executable_peak_bytes", 0))
        tol = max(int(0.10 * int(r.get("device_bytes_in_use", 0))),
                  1 << 20)
        if unattr > bound + tol:
            out.append(
                f"  MISMATCH at {r.get('source', '?')} step "
                f"{r.get('step', '?')}: unattributed "
                f"{_fmt_bytes(unattr)} exceeds executable peaks "
                f"{_fmt_bytes(bound)} (+{_fmt_bytes(tol)} tolerance)")
    out.append("")


def section_events(recs, out, top):
    evs = [r for r in recs if r.get("kind") == "event"]
    if not evs:
        return
    stragglers = [e for e in evs if e.get("event") == "straggler"]
    out.append(f"== events ==  ({len(evs)} total, "
               f"{len(stragglers)} straggler(s))")
    for e in stragglers:
        out.append(
            f"  STRAGGLER rank {e.get('straggler_rank', '?')} at step "
            f"{e.get('step', '?')}: "
            f"{_fmt_s(float(e.get('step_time_s', 0.0)))} vs median "
            f"{_fmt_s(float(e.get('median_s', 0.0)))} "
            f"(lag {_fmt_s(float(e.get('lag_s', 0.0)))})")
    others = [e for e in evs if e.get("event") != "straggler"]
    counts = {}
    for e in others:
        counts[e.get("event", "?")] = counts.get(e.get("event", "?"), 0) + 1
    if counts:
        out.append("  other: " + "  ".join(
            f"{k}={v}" for k, v in sorted(counts.items())))
    out.append("")


def section_lint(recs, out, top):
    """Static-analysis findings (kind:"lint" — tools/paddlelint.py,
    docs/STATIC_ANALYSIS.md): unsuppressed findings are the headline
    (a clean run renders none), suppressions roll up per pass."""
    lints = [r for r in recs if r.get("kind") == "lint"]
    if not lints:
        return
    live = [r for r in lints if not r.get("suppressed")]
    sup = [r for r in lints if r.get("suppressed")]
    out.append(f"== lint ==  ({len(live)} finding(s), {len(sup)} "
               "suppressed with reasons)")
    for r in live[:max(top, 5)]:
        out.append(
            f"  {r.get('severity', '?').upper()} "
            f"[{r.get('pass', '?')}/{r.get('rule', '?')}] "
            f"{r.get('file', '?')}:{r.get('line', '?')} "
            f"{str(r.get('message', ''))[:100]}")
    if len(live) > max(top, 5):
        out.append(f"  ... and {len(live) - max(top, 5)} more")
    by_pass = {}
    for r in sup:
        by_pass[r.get("pass", "?")] = by_pass.get(r.get("pass", "?"),
                                                  0) + 1
    if by_pass:
        out.append("  suppressed: " + "  ".join(
            f"{k}={v}" for k, v in sorted(by_pass.items())))
    out.append("")


def render(recs, top=5):
    out = []
    ranks = sorted({r.get("rank", 0) for r in recs})
    kinds = {}
    for r in recs:
        kinds[r.get("kind", "?")] = kinds.get(r.get("kind", "?"), 0) + 1
    out.append(f"run summary: {len(recs)} records, rank(s) "
               f"{','.join(str(r) for r in ranks)}  [" + "  ".join(
                   f"{k}:{v}" for k, v in sorted(kinds.items())) + "]")
    out.append("")
    section_steps(recs, out)
    section_compiles(recs, out, top)
    section_serve(recs, out)
    section_routing(recs, out)
    section_journeys(recs, out)
    section_fleet(recs, out)
    section_memory(recs, out)
    section_collectives(recs, out, top)
    section_ranks(recs, out)
    section_events(recs, out, top)
    section_lint(recs, out, top)
    return "\n".join(out).rstrip() + "\n"


def main(argv=None):
    ap = argparse.ArgumentParser(
        "obs_report", description="human run summary from a paddle_tpu "
                                  "metrics JSONL")
    ap.add_argument("files", nargs="+", help="metrics JSONL file(s) — "
                    "several rank files render as one run")
    ap.add_argument("--top", type=int, default=5,
                    help="rows per top-k table (default 5)")
    args = ap.parse_args(argv)
    recs = []
    for path in args.files:
        try:
            recs.extend(load_records(path))
        except OSError as e:
            print(f"obs_report: {e}", file=sys.stderr)
            return 2
    if not recs:
        print("obs_report: no records in input", file=sys.stderr)
        return 2
    sys.stdout.write(render(recs, top=args.top))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
