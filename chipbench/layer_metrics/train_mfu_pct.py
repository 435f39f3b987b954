"""Model FLOP utilisation: ``flops.train_flops_per_token`` (recompute not
counted) x tokens/s per chip over the chip's bf16 peak. An end-to-end
utilisation, not a kernel's roofline share."""
from .. import flops

NAME, UNIT, LAYER = "train_mfu_pct", "%", "training engine"


def read(ctx):
    t = ctx["train"]
    if not t:
        return None
    rate = len(t["steps"]) * t["tokens_per_step"] / ctx["window_s"] / ctx["chips"]
    per_token = flops.train_flops_per_token(ctx["program"], t["sequence_length"])
    return 100.0 * rate * per_token / ctx["peak"]["bf16_flops_per_s"]
