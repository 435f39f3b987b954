"""Roofline share of the grouped matmuls of the routed layers in the prefill
programs of a model that holds a SHARE of its experts (``jax.lax.ragged_dot``,
which the TPU compiler turns into a grouped-GEMM kernel the trace names
``ragged-dot...``): the least time the chip could take for the grouped matmuls of
the traced prefills (``kinds_cost.grouped_gemm_cost`` of the pairs their spans
say were dispatched to held experts, ``expert_rows_held``; the bound is printed)
over those operations' summed device time in the trace. The prefills counted are
those long enough to take the sorted form (``kinds_cost.SORTED_FORM_ROWS``: a
shorter one computes every held expert on every row and runs no grouped matmul).
Absent where no such operation ran or no span counts the held pairs."""
from .. import flops, kinds_cost
from ..reduce import op_seconds_matching
from . import span_ring as R

NAME, UNIT, LAYER = "held_moe_gemm_roofline_pct", "%", "kernels"
KERNELS = r"prefill\S*/ragged-dot"  # by the operation's name: <program>/<instruction>


def read(ctx):
    tr = ctx["trace"]
    if not tr or "moe_experts_held" not in ctx["program"]:
        return None
    seconds = op_seconds_matching(tr, KERNELS)
    calls = [call for call, _, _ in R.calls(R.started_in(R.serve_window(ctx, "traced")),
                                            "prefill")]
    calls = [c for c in calls if "expert_rows_held" in c.attrs
             and c.attrs["bucket"] > kinds_cost.SORTED_FORM_ROWS]
    if seconds <= 0 or not calls:
        return None
    costs = [kinds_cost.grouped_gemm_cost(ctx["program"], c.attrs["expert_rows_held"])
             for c in calls]
    cost = {k: sum(c[k] for c in costs) for k in ("flops", "bytes")}
    share = flops.roofline(cost, seconds, ctx["peak"])
    ctx["run"].note(event="roofline", kernel="ragged-dot", seconds=seconds, prefills=len(calls),
                    expert_rows_held=sum(c.attrs["expert_rows_held"] for c in calls), **share)
    return share["pct"]
