"""The prefill programs' share of the chip's peak for a model whose layer stack runs
several times over the same weights: the operations one prefill of its bucket
requires (``loop_cost.prefill_flops``: the layers' parameters on a token's path,
every pass's, x the bucket's rows, the head for one row, causal attention at its
half in passes x layers) over peak FLOP/s and the span's own duration; the median
over the window's ``.../prefill`` spans, as ``prefill_mfu_pct`` is built. An
end-to-end utilisation of those programs, padding counted as work. A program
without ``layer_passes``, or whose spans lack it, gives nothing."""
import numpy as np

from .. import loop_cost
from . import span_ring as R

NAME, UNIT, LAYER = "loop_prefill_mfu_pct", "%", "serving device programs"


def read(ctx):
    if not ctx["serve"] or "layer_passes" not in ctx["program"]:
        return None
    calls = [call for call, _, _ in R.calls(R.started_in(R.serve_window(ctx)), "prefill")]
    calls = [c for c in calls if "layer_passes" in c.attrs]
    if not calls:
        return None
    peak = ctx["peak"]["bf16_flops_per_s"]
    by_bucket = {}
    for c in calls:
        by_bucket.setdefault(c.attrs["bucket"], []).append(c.t1 - c.t0)
    ctx["run"].note(event="roofline", program="prefill", prefills=len(calls),
                    ms_p50_by_bucket={b: 1e3 * float(np.median(t))
                                      for b, t in sorted(by_bucket.items())})
    return 100.0 * float(np.median([
        loop_cost.prefill_flops(ctx["program"], c.attrs["bucket"]) / peak / (c.t1 - c.t0)
        for c in calls]))
