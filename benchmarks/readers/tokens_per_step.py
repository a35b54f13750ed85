"""Prefill and output tokens of the window over the scheduler steps the
engine counted in it (monitor histogram "serve.batch_size": one
observation per step)."""


def read(ctx):
    w = ctx["window"]
    return w["tokens_processed"] / w["steps"] if w.get("steps") else None
