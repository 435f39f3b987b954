"""Roofline share of the Pallas flash-attention kernels (forward, dK/dV, dQ)
in a training step: the least time the chip could take for the calls the
traced steps made (``flops.flash_cost``) over the kernels' summed device time
in the trace. Compute-bound at these shapes; the bound is printed."""
from .. import flops
from ..reduce import op_seconds_matching

NAME, UNIT, LAYER = "flash_roofline_pct", "%", "kernels"
# the trace names a Pallas kernel only by its custom-call target; in the train
# step the three flash kernels (forward, dK/dV, dQ) are the only such calls
KERNELS = r'custom_call_target="tpu_custom_call"'


def read(ctx):
    tr, t = ctx["trace"], ctx["train"]
    if not tr or not t or not t.get("traced_steps"):
        return None
    seconds = op_seconds_matching(tr, KERNELS)
    if seconds <= 0:
        return None  # the kernel did not run here
    p = ctx["program"]
    per_chip = t["sequences_per_step"] // ctx["chips"]
    shape = (per_chip, t["sequence_length"], p["num_heads"], p["hidden_size"] // p["num_heads"])
    fwd, bwd = flops.flash_cost(*shape), flops.flash_cost(*shape, backward=True)
    calls = t["traced_steps"] * p["num_layers"]
    cost = {k: calls * (fwd[k] + bwd[k]) for k in ("flops", "bytes")}
    share = flops.roofline(cost, seconds, ctx["peak"])
    ctx["run"].note(event="roofline", kernel="flash", seconds=seconds, **share)
    return share["pct"]
