"""The prefill program's share of the chip's peak for a model with latent
attention: the operations one prefill of its bucket requires
(``mla_cost.prefill_flops``: the parameters on a token's path x the bucket's
rows, ``W_kv_b``'s expansion among them, the head for one row, expanded causal
attention at its half over q/k heads of 192 and value heads of 128) over peak
FLOP/s and the span's own duration; the median over the window's
``.../prefill`` spans. An end-to-end utilisation of that program, padding
counted as work."""
import numpy as np

from .. import mla_cost
from . import span_ring as R

NAME, UNIT, LAYER = "latent_prefill_mfu_pct", "%", "serving device programs"


def read(ctx):
    if "kv_lora_rank" not in ctx["program"]:
        return None
    calls = [call for call, _, _ in R.calls(R.started_in(R.serve_window(ctx)), "prefill")]
    if not calls:
        return None
    peak = ctx["peak"]["bf16_flops_per_s"]
    return 100.0 * float(np.median([
        mla_cost.prefill_flops(ctx["program"], c.attrs["bucket"]) / peak / (c.t1 - c.t0)
        for c in calls]))
