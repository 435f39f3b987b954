"""Roofline share of the Pallas flash forward kernel in the prefill programs of a
model with window and whole-context layers (the trace names the kernel's
operations ``.../flash_fwd...``): the least time the chip could take for the
attention the traced prefills REQUIRE (``kinds_cost.flash_cost``: the causal half
in a whole-context layer, min(position + 1, window) keys a query in a window
layer; the bound is printed) over those operations' summed device time in the
trace. It reads low while a window layer runs the whole causal grid under its
mask: the kernel's time is the grid's, the requirement the window's. Counted are
the prefills whose span's ``attn`` says ``flash``. Absent where no such operation
ran (a bucket that attends densely, a program without window layers)."""
from .. import flops, kinds_cost
from ..reduce import op_seconds_matching
from . import span_ring as R

NAME, UNIT, LAYER = "kinds_flash_roofline_pct", "%", "kernels"
KERNELS = r"prefill\S*/flash_fwd"  # by the operation's name: <program>/<instruction>


def read(ctx):
    tr = ctx["trace"]
    if not tr or "local_attn_layers" not in ctx["program"]:
        return None
    seconds = op_seconds_matching(tr, KERNELS)
    calls = [call for call, _, _ in R.calls(R.started_in(R.serve_window(ctx, "traced")),
                                            "prefill")]
    calls = [c for c in calls if "flash" in str(c.attrs.get("attn")) and "ring_tokens" in c.attrs]
    if seconds <= 0 or not calls:
        return None
    costs = [kinds_cost.flash_cost(ctx["program"], c.attrs["bucket"]) for c in calls]
    cost = {k: sum(c[k] for c in costs) for k in ("flops", "bytes")}
    share = flops.roofline(cost, seconds, ctx["peak"])
    ctx["run"].note(event="roofline", kernel="flash_fwd", seconds=seconds,
                    prefills=len(calls), **share)
    return share["pct"]
