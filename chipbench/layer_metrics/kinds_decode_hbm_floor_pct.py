"""The decode program's share of its memory roofline for a model with window and
whole-context layers that holds a share of its experts: the least time the chip
needs to read what one decode step must (``kinds_cost.decode_min_bytes``: the
matmul weights outside the experts, the held experts the step touched, the live
positions of the whole-context layers and the rings' of the window layers) over
the median ``.../decode`` span. The counts are the medians of the spans' own
``cached_tokens``, ``ring_tokens`` and ``experts_touched``; a program whose spans
lack any (one without rings or routing) gives nothing."""
import numpy as np

from .. import kinds_cost
from . import span_ring as R

NAME, UNIT, LAYER = "kinds_decode_hbm_floor_pct", "%", "serving device programs"
NEEDS = ("cached_tokens", "ring_tokens", "experts_touched")


def read(ctx):
    if not ctx["serve"] or "local_attn_layers" not in ctx["program"]:
        return None
    calls = [call for call, _, _ in R.calls(R.started_in(R.serve_window(ctx)), "decode")]
    calls = [c for c in calls if all(key in c.attrs for key in NEEDS)]
    if not calls:
        return None
    cached, ring, touched = (float(np.median([c.attrs[key] for c in calls])) for key in NEEDS)
    need = kinds_cost.decode_min_bytes(ctx["program"], cached, ring, touched)
    floor_ms = 1e3 * need / ctx["peak"]["hbm_bytes_per_s"]
    step_ms = R.median_ms(calls)
    ctx["run"].note(event="roofline", program="decode", floor_ms=floor_ms, step_ms=step_ms,
                    bytes=need, cached_tokens=cached, ring_tokens=ring, experts_touched=touched)
    return 100.0 * floor_ms / step_ms
