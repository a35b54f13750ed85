"""The whole step's share of the chips' bf16 peak: required FLOPs of the
window (forward + backward per token in training, recompute not counted;
every prefill and decode token's forward in serving) over window x chips
x peak. Required FLOPs come from benchmarks/lib/work.py."""
from ..lib import work


def read(ctx):
    w, cfg = ctx["window"], ctx["config"]
    if w["kind"] == "train":
        if cfg["reference"] != "gpt":
            return None
        flops = work.gpt_train_flops_per_token(cfg, w["seq"]) * w["tokens"]
    else:
        flops = w["required_flops"]
    return 100.0 * flops / (w["window_s"] * ctx["chips"]
                            * ctx["peak"]["bf16_flops"])
