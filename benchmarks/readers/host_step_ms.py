"""Mean host time of one call into the step (enqueue, not step time)."""


def read(ctx):
    h = ctx["window"].get("host_step_s")
    return 1e3 * sum(h) / len(h) if h else None
