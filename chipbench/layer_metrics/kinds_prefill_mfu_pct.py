"""The prefill programs' share of the chip's peak for a model with window and
whole-context layers that holds a share of its experts: the operations the
window's prefills require (``kinds_cost.prefill_flops`` of each span's bucket and
its own ``expert_rows_held``: the parameters outside the experts x the bucket's
rows, the head for one row, one expert's parameters x the pairs dispatched to
held experts, attention at what the model requires: the causal half in a
whole-context layer, min(position + 1, window) keys a query in a window layer)
over peak FLOP/s and the time those spans took: the SUM of the operations over
the SUM of the durations of the window's ``.../prefill`` spans (the form
``ssm_prefill_mfu_pct`` took for a cell of several buckets). An end-to-end
utilisation of those programs, padding counted as work. A program whose spans
lack ``expert_rows_held`` gives nothing."""
from .. import kinds_cost
from . import span_ring as R

NAME, UNIT, LAYER = "kinds_prefill_mfu_pct", "%", "serving device programs"


def read(ctx):
    if not ctx["serve"] or "local_attn_layers" not in ctx["program"]:
        return None
    calls = [call for call, _, _ in R.calls(R.started_in(R.serve_window(ctx)), "prefill")]
    calls = [c for c in calls if "expert_rows_held" in c.attrs and "ring_tokens" in c.attrs]
    if not calls:
        return None
    flops = sum(kinds_cost.prefill_flops(ctx["program"], c.attrs["bucket"],
                                         c.attrs["expert_rows_held"]) for c in calls)
    seconds = sum(c.t1 - c.t0 for c in calls)
    ctx["run"].note(event="roofline", program="prefill", prefills=len(calls), seconds=seconds,
                    flops=flops, rows=sum(c.attrs["bucket"] for c in calls),
                    expert_rows_held=sum(c.attrs["expert_rows_held"] for c in calls))
    return 100.0 * flops / ctx["peak"]["bf16_flops_per_s"] / seconds
