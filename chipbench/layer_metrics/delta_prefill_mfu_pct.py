"""The prefill programs' share of the chip's peak for a model with attention and
gated-delta-rule layers and a held share of a routed feed-forward: the operations
the window's prefills require (``delta_cost.prefill_flops`` of each span's LIVE
rows, ``state_rows``: the parameters on a token's path x the rows, the head for one
row, causal attention at its half in the ATTENTION layers alone, the rule at its
recurrent cost of 6 x Hv x D x D a row a delta layer and the filter) over peak
FLOP/s and the time those spans took: the SUM of the operations over the SUM of
the durations of the window's ``.../prefill`` spans (the form
``ssm_prefill_mfu_pct`` took for a cell of several buckets). An end-to-end
utilisation of those programs; a bucket's padding is time spent and no work done.
A program whose spans lack ``scan_chunks`` or ``delta_layers`` gives nothing."""
from .. import delta_cost
from . import span_ring as R

NAME, UNIT, LAYER = "delta_prefill_mfu_pct", "%", "serving device programs"
NEEDS = ("scan_chunks", "delta_layers", "state_rows")


def read(ctx):
    if not ctx["serve"] or "delta_head_dim" not in ctx["program"]:
        return None
    calls = [call for call, _, _ in R.calls(R.started_in(R.serve_window(ctx)), "prefill")]
    calls = [c for c in calls if all(key in c.attrs for key in NEEDS)]  # ran the rule's block form
    if not calls:
        return None
    flops = sum(delta_cost.prefill_flops(ctx["program"], c.attrs["state_rows"]) for c in calls)
    seconds = sum(c.t1 - c.t0 for c in calls)
    ctx["run"].note(event="roofline", program="prefill", prefills=len(calls), seconds=seconds,
                    flops=flops, live_rows=sum(c.attrs["state_rows"] for c in calls),
                    rows=sum(c.attrs["bucket"] for c in calls))
    return 100.0 * flops / ctx["peak"]["bf16_flops_per_s"] / seconds
