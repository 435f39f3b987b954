"""Roofline share of the Pallas decode-attention kernel: the least time the
chip could take to read the keys and values actually live in each traced
decode step (``flops.decode_attention_cost``; memory-bound) over the kernel's
summed device time in the trace. Absent where the kernel does not run."""
from .. import flops
from ..reduce import op_seconds_matching

NAME, UNIT, LAYER = "decode_attn_roofline_pct", "%", "kernels"
# the trace names a Pallas kernel only by its custom-call target; in the
# decode program the decode-attention kernel is the only such call
KERNELS = r'custom_call_target="tpu_custom_call"'


def read(ctx):
    tr, s = ctx["trace"], ctx["serve"]
    if not tr or not s or s["traced"][0] is None:
        return None
    seconds = op_seconds_matching(tr, KERNELS)
    if seconds <= 0:
        return None  # the kernel did not run here
    t0, t1 = s["traced"]
    live = sum(n for ts, te, _, n in s["steps"] if t0 <= ts and te <= t1)
    p = ctx["program"]
    cost = flops.decode_attention_cost(live, p["num_heads"], p["hidden_size"] // p["num_heads"],
                                       p["num_layers"])
    share = flops.roofline(cost, seconds, ctx["peak"])
    ctx["run"].note(event="roofline", kernel="decode_attention", seconds=seconds, **share)
    return share["pct"]
