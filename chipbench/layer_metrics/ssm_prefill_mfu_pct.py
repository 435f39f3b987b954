"""The prefill programs' share of the chip's peak for a model with a
state-space mixer beside grouped-query attention: the operations the window's
prefills require (``ssm_cost.prefill_flops`` of each span's bucket: the
parameters on a token's path x the bucket's rows, the head for one row, causal
attention at its half over the query heads, the scan at its recurrent cost of
6 x H x P x N a row a layer) over peak FLOP/s and the time those spans took:
the SUM of the operations over the SUM of the durations of the window's
``.../prefill`` spans. (A median of the spans' own shares sits on whichever of
the cell's five buckets the middle span has and moves by 40% between two
windows of one program; the window's operations over the window's prefill time
does not.) An end-to-end utilisation of those programs, padding counted as
work."""
from .. import ssm_cost
from . import span_ring as R

NAME, UNIT, LAYER = "ssm_prefill_mfu_pct", "%", "serving device programs"


def read(ctx):
    if not ctx["serve"] or "ssm_state_size" not in ctx["program"]:
        return None
    calls = [call for call, _, _ in R.calls(R.started_in(R.serve_window(ctx)), "prefill")]
    calls = [c for c in calls if "scan_chunks" in c.attrs]  # a program that ran the scan
    if not calls:
        return None
    flops = sum(ssm_cost.prefill_flops(ctx["program"], c.attrs["bucket"]) for c in calls)
    return 100.0 * flops / ctx["peak"]["bf16_flops_per_s"] / sum(c.t1 - c.t0 for c in calls)
