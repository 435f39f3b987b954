"""The decode program's share of its memory roofline: the least time the chip
needs to read what one decode step must (``moe_cost.decode_min_bytes``: the
matmul weights outside the experts, the experts the step touched, the live keys
and values, in the compute dtype) over the median ``.../decode`` span. The
experts touched are the median of the spans' ``experts_touched``; the live
tokens the median over the window's steps. A routed model whose spans lack the
attribute (a program from before it) gives nothing."""
import numpy as np

from .. import moe_cost
from . import span_ring as R

NAME, UNIT, LAYER = "decode_hbm_floor_pct", "%", "serving device programs"


def read(ctx):
    s = ctx["serve"]
    calls = [call for call, _, _ in R.calls(R.started_in(R.serve_window(ctx)), "decode")]
    if not s or not calls:
        return None
    lo, hi = s["window"]
    live = [n for ts, te, _, n in s["steps"] if lo <= ts and te <= hi and n > 0]
    touched = [c.attrs["experts_touched"] for c in calls if "experts_touched" in c.attrs]
    touched = float(np.median(touched)) if touched else None
    try:
        need = moe_cost.decode_min_bytes(ctx["program"], float(np.median(live)), touched)
    except ValueError:
        return None
    floor_ms = 1e3 * need / ctx["peak"]["hbm_bytes_per_s"]
    step_ms = R.median_ms(calls)
    ctx["run"].note(event="roofline", program="decode", floor_ms=floor_ms, step_ms=step_ms,
                    bytes=need, experts_touched=touched)
    return 100.0 * floor_ms / step_ms
