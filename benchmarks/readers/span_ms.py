"""Mean host time per call, in ms, of one of the program's own spans.

The program records every closed span itself (name, start, duration,
thread, depth: paddle_tpu.profiler.statistic.closed_spans, over the
flight recorder's ring). Of the ring's last `window["steps"]` closed
`parent` spans (fewer if it holds fewer) this takes `span`'s time inside
them: same thread, inside the parent's interval, so a span of the same
name on another thread is not counted. With `span` None it is the
parent's self time: its duration less what its direct children cover.
A child that never ran inside those parents reads 0. None when the
parent never ran, or the program has no such recorder (older commits).

A ring that has wrapped may have dropped children of the oldest parent
it still holds; that is one call among those averaged."""


def program_spans():
    try:
        from paddle_tpu.profiler import statistic
    except ImportError:
        return None
    closed = getattr(statistic, "closed_spans", None)
    return closed() if closed else None


def mean_ms(spans, parent, span, calls):
    """`spans`: dicts of name, start_s, dur_s, thread, depth, in the
    order they closed."""
    parents = [s for s in spans if s["name"] == parent][-calls:] \
        if calls > 0 else []
    if not parents:
        return None
    total = 0.0
    for p in parents:
        lo, hi = p["start_s"], p["start_s"] + p["dur_s"]
        inside = [s for s in spans
                  if s is not p and s["thread"] == p["thread"]
                  and s["start_s"] >= lo and s["start_s"] + s["dur_s"] <= hi]
        if span is None:
            total += p["dur_s"] - sum(s["dur_s"] for s in inside
                                      if s["depth"] == p["depth"] + 1)
        else:
            total += sum(s["dur_s"] for s in inside if s["name"] == span)
    return 1e3 * total / len(parents)


def read(ctx, parent, span=None):
    spans = program_spans()
    if spans is None:
        return None
    return mean_ms(spans, parent, span, int(ctx["window"]["steps"]))
