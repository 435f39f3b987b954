"""The prefill program's share of the chip's peak: the operations one prefill
of its bucket requires (``moe_cost.prefill_flops``: the parameters on a token's
path x the bucket's rows, the head for one row, causal attention at its half)
over peak FLOP/s and the span's own duration; the median over the window's
``.../prefill`` spans. An end-to-end utilisation of that program, padding
counted as work."""
import numpy as np

from .. import moe_cost
from . import span_ring as R

NAME, UNIT, LAYER = "prefill_mfu_pct", "%", "serving device programs"


def read(ctx):
    calls = [call for call, _, _ in R.calls(R.started_in(R.serve_window(ctx)), "prefill")]
    if not calls:
        return None
    peak = ctx["peak"]["bf16_flops_per_s"]
    return 100.0 * float(np.median([
        moe_cost.prefill_flops(ctx["program"], c.attrs["bucket"]) / peak / (c.t1 - c.t0)
        for c in calls]))
