"""The flash-attention kernels' share of their roofline over the traced
window, for grouped-query attention with full and windowed layers mixed
(kernel_roofline.py's arithmetic, the required work from
lib/work_swa_moe.py: the visible pairs of each layer's own mask, k and v
at the key/value heads' count): the least time the chip could take over
the device time under the kernels' names. Steps in the window from dq's
calls (1 a layer and step, whatever is rematerialised). None where the
kernels never ran."""
from ..lib import work, work_swa_moe


def read(ctx, kernels):
    w, cfg = ctx["window"], ctx["config"]
    seconds, _ = ctx["trace"].kernel_seconds(
        "|".join(f"(?:{k})" for k in kernels))
    _, calls = ctx["trace"].kernel_seconds("flash_attention_dq")
    if not calls or seconds <= 0:
        return None
    steps = calls / float(cfg["num_hidden_layers"])
    one = work_swa_moe.flash_work(cfg, w["batch"], w["seq"])
    least, _ = work.roofline_seconds(
        sum(one[k]["flops"] for k in kernels) * steps / ctx["chips"],
        sum(one[k]["bytes"] for k in kernels) * steps / ctx["chips"],
        ctx["peak"])
    return 100.0 * least / seconds
