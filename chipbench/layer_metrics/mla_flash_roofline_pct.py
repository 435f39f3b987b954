"""Roofline share of the Pallas flash forward kernel in the prefill program of
a model with latent attention (q/k heads of 192, value heads of 128; the trace
names the kernel's operations ``.../flash_fwd...``, the name the kernel gives
its call): the least time the chip could take for the traced prefills' calls
(``mla_cost.flash_cost``; compute-bound at these shapes, the bound is printed)
over those operations' summed device time in the trace. Absent where no such
operation ran (a bucket that attends densely, a program without the kernel)."""
from .. import flops, mla_cost
from ..reduce import op_seconds_matching
from . import span_ring as R

NAME, UNIT, LAYER = "mla_flash_roofline_pct", "%", "kernels"
KERNELS = r"prefill\S*/flash_fwd"  # by the operation's name: <program>/<instruction>


def read(ctx):
    tr = ctx["trace"]
    if not tr or "kv_lora_rank" not in ctx["program"]:
        return None
    seconds = op_seconds_matching(tr, KERNELS)
    calls = [call for call, _, _ in R.calls(R.started_in(R.serve_window(ctx, "traced")),
                                            "prefill")]
    calls = [c for c in calls if c.attrs.get("attn") == "flash"]
    if seconds <= 0 or not calls:
        return None
    costs = [mla_cost.flash_cost(ctx["program"], c.attrs["bucket"]) for c in calls]
    cost = {k: sum(c[k] for c in costs) for k in ("flops", "bytes")}
    share = flops.roofline(cost, seconds, ctx["peak"])
    ctx["run"].note(event="roofline", kernel="flash_fwd", seconds=seconds,
                    prefills=len(calls), **share)
    return share["pct"]
