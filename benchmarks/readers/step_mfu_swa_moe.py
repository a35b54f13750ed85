"""The whole training step's share of the chip's bf16 peak for the
windowed grouped-query decoder with pre-routed experts: required FLOPs
of the window (lib/work_swa_moe.py: attention counted causally and
inside the band by layer type, the experts' part from the COUNTED share
of assignments that went to held experts) over window x chips x peak.
None where the program counts no assignments."""
from ..lib import work_swa_moe
from .moe_counters import local_share


def read(ctx):
    w, cfg = ctx["window"], ctx["config"]
    share = local_share()
    if share is None:
        return None
    local = (share * w["tokens"] * cfg["moe_num_active_primary_experts"]
             * cfg["num_hidden_layers"])
    flops = work_swa_moe.train_flops(cfg, w["seq"], w["tokens"], local)
    return 100.0 * flops / (w["window_s"] * ctx["chips"]
                            * ctx["peak"]["bf16_flops"])
