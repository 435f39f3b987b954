"""Roofline share of the grouped matmuls of the routed layers in the prefill
program (``jax.lax.ragged_dot``, which the TPU compiler turns into a
grouped-GEMM kernel the trace names ``ragged-dot...``): the least time the chip
could take for the traced prefills' grouped matmuls
(``moe_cost.grouped_gemm_cost``: compute and the experts' weights are within a
few percent of each other at 2048 rows; the bound is printed) over those
operations' summed device time in the trace. Absent where no such operation
ran."""
from .. import flops, moe_cost
from ..reduce import op_seconds_matching
from . import span_ring as R

NAME, UNIT, LAYER = "moe_gemm_roofline_pct", "%", "kernels"
KERNELS = r"prefill\S*/ragged-dot"  # by the operation's name: <program>/<instruction>


def read(ctx):
    tr = ctx["trace"]
    if not tr or "moe_top_k" not in ctx["program"]:
        return None
    seconds = op_seconds_matching(tr, KERNELS)
    calls = [call for call, _, _ in R.calls(R.started_in(R.serve_window(ctx, "traced")),
                                            "prefill")]
    if seconds <= 0 or not calls:
        return None
    costs = [moe_cost.grouped_gemm_cost(ctx["program"], c.attrs["bucket"]) for c in calls]
    cost = {k: sum(c[k] for c in costs) for k in ("flops", "bytes")}
    share = flops.roofline(cost, seconds, ctx["peak"])
    ctx["run"].note(event="roofline", kernel="ragged-dot", seconds=seconds,
                    prefills=len(calls), **share)
    return share["pct"]
