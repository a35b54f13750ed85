"""A kernel's share of its roofline over the traced window: the least
time the chip could take for the work the kernel was REQUIRED to do (the
larger of FLOPs over peak FLOP/s and bytes over peak bytes/s, from
benchmarks/lib/work.py) over the device time the trace shows under the
kernel's name on the fullest device. Where the kernel never ran there is
nothing to read, and no value.

args: kernels — name patterns whose time is summed; kind — which
function below describes the required work."""
from ..lib import work


def flash_attention(ctx, kernels):
    w, cfg = ctx["window"], ctx["config"]
    L = cfg["num_layers"]
    per_layer = work.flash_attention_work(
        w["batch"], cfg["num_heads"], w["seq"],
        cfg["hidden_size"] // cfg["num_heads"])
    # steps inside the traced window: dq runs once per layer per step on
    # each device, whatever the program rematerialises
    _, dq_calls = ctx["trace"].kernel_seconds("flash_attention_dq")
    steps = dq_calls / L
    flops = sum(per_layer[k]["flops"] for k in kernels) * L * steps
    nbytes = sum(per_layer[k]["bytes"] for k in kernels) * L * steps
    return flops / ctx["chips"], nbytes / ctx["chips"]


def ragged_attention(ctx, kernels):
    w, cfg = ctx["window"], ctx["config"]
    one = work.ragged_rows_work(
        w["traced_rows"], cfg["num_heads"],
        cfg["hidden_size"] // cfg["num_heads"], w["page_size"])
    return one["flops"] * cfg["num_layers"], one["bytes"] * cfg["num_layers"]


def ssm_scan(ctx, kernels):
    w, cfg = ctx["window"], ctx["config"]
    d, N, _, _ = work.mamba_dims(cfg)
    rows = w["traced_rows"]
    tokens = sum(n for n, _ in rows)
    L = cfg["num_layers"]
    return (work.ssm_scan_flops_per_token(d, N) * tokens * L,
            work.ssm_scan_bytes(tokens, len(rows), d, N) * L)


KINDS = {"flash_attention": flash_attention,
         "ragged_attention": ragged_attention, "ssm_scan": ssm_scan}


def read(ctx, kernels, kind):
    seconds, calls = ctx["trace"].kernel_seconds(
        "|".join(f"(?:{k})" for k in kernels))
    if not calls or seconds <= 0:
        return None
    flops, nbytes = KINDS[kind](ctx, kernels)
    least, _ = work.roofline_seconds(flops, nbytes, ctx["peak"])
    return 100.0 * least / seconds
