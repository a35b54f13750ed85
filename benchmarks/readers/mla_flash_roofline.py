"""The flash-attention kernels' share of their roofline over the traced
window, for latent attention (kernel_roofline.py's arithmetic, with the
head dim read from this configuration's own keys, nope + rope, where
that reader takes hidden / heads): the least time the chip could take
for the REQUIRED work over the device time under the kernels' names.
Steps in the window from dq's calls (1 a layer and step, whatever is
rematerialised). None where the kernels never ran."""
from ..lib import work, work_moe


def read(ctx, kernels):
    w, cfg = ctx["window"], ctx["config"]
    seconds, _ = ctx["trace"].kernel_seconds(
        "|".join(f"(?:{k})" for k in kernels))
    _, calls = ctx["trace"].kernel_seconds("flash_attention_dq")
    if not calls or seconds <= 0:
        return None
    one = work_moe.mla_flash_work(cfg, w["batch"], w["seq"])
    least, _ = work.roofline_seconds(
        sum(k["flops"] for k in one.values()) * calls / ctx["chips"],
        sum(k["bytes"] for k in one.values()) * calls / ctx["chips"],
        ctx["peak"])
    return 100.0 * least / seconds
