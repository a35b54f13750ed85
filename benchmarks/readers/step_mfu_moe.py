"""The whole training step's share of the chip's bf16 peak for the
expert-layer decoder: required FLOPs of the window (lib/work_moe.py, with
the COUNTED share of assignments that went to held experts) over window
x chips x peak. None where the program counts no assignments."""
from ..lib import work_moe
from .moe_counters import local_share


def read(ctx):
    w, cfg = ctx["window"], ctx["config"]
    share = local_share()
    if share is None:
        return None
    n_moe = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    local = share * w["tokens"] * cfg["num_experts_per_tok"] * n_moe
    flops = work_moe.train_flops(cfg, w["seq"], w["tokens"], local)
    return 100.0 * flops / (w["window_s"] * ctx["chips"]
                            * ctx["peak"]["bf16_flops"])
