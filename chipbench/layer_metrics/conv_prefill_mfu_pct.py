"""The prefill programs' share of the chip's peak for a model with attention and
short-convolution layers and a routed feed-forward: the operations the window's
prefills require (``conv_cost.prefill_flops`` of each span's bucket: the
parameters on a token's path x the bucket's rows, the head for one row, causal
attention at its half in the ATTENTION layers alone, the filter at 2 x taps x
channels a row a conv layer) over peak FLOP/s and the time those spans took: the
SUM of the operations over the SUM of the durations of the window's
``.../prefill`` spans (the form ``ssm_prefill_mfu_pct`` took for a cell of several
buckets). An end-to-end utilisation of those programs, padding counted as work. A
program whose spans lack ``conv_layers`` gives nothing."""
from .. import conv_cost
from . import span_ring as R

NAME, UNIT, LAYER = "conv_prefill_mfu_pct", "%", "serving device programs"


def read(ctx):
    if not ctx["serve"] or "layer_operators" not in ctx["program"]:
        return None
    calls = [call for call, _, _ in R.calls(R.started_in(R.serve_window(ctx)), "prefill")]
    calls = [c for c in calls if "conv_layers" in c.attrs]  # a program that ran the operator
    if not calls:
        return None
    flops = sum(conv_cost.prefill_flops(ctx["program"], c.attrs["bucket"]) for c in calls)
    seconds = sum(c.t1 - c.t0 for c in calls)
    ctx["run"].note(event="roofline", program="prefill", prefills=len(calls), seconds=seconds,
                    flops=flops, rows=sum(c.attrs["bucket"] for c in calls))
    return 100.0 * flops / ctx["peak"]["bf16_flops_per_s"] / seconds
