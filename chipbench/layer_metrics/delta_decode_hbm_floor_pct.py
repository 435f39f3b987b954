"""The decode program's share of its memory roofline for a model with attention
and gated-delta-rule layers and a held share of a routed feed-forward: the least
time the chip needs to move what one decode step must
(``delta_cost.decode_min_bytes``: the matmul weights outside the experts, the held
experts the step touched, the live positions of the ATTENTION layers alone, the
delta state of the rows it advanced read AND written) over the median
``.../decode`` span. The counts are the medians of the spans' own
``cached_tokens``, ``state_bytes`` and ``experts_touched``; a program whose spans
lack any, or ``delta_layers`` (one without the operator), gives nothing."""
import numpy as np

from .. import delta_cost
from . import span_ring as R

NAME, UNIT, LAYER = "delta_decode_hbm_floor_pct", "%", "serving device programs"
NEEDS = ("cached_tokens", "state_bytes", "experts_touched", "delta_layers")


def read(ctx):
    if not ctx["serve"] or "delta_head_dim" not in ctx["program"]:
        return None
    calls = [call for call, _, _ in R.calls(R.started_in(R.serve_window(ctx)), "decode")]
    calls = [c for c in calls if all(key in c.attrs for key in NEEDS)]
    if not calls:
        return None
    cached, state, touched = (float(np.median([c.attrs[key] for c in calls]))
                              for key in NEEDS[:3])
    need = delta_cost.decode_min_bytes(ctx["program"], cached, state, touched)
    floor_ms = 1e3 * need / ctx["peak"]["hbm_bytes_per_s"]
    step_ms = R.median_ms(calls)
    ctx["run"].note(event="roofline", program="decode", floor_ms=floor_ms, step_ms=step_ms,
                    bytes=need, cached_tokens=cached, state_bytes=state, experts_touched=touched,
                    state_rows=float(np.median([c.attrs.get("state_rows", 0) for c in calls])))
    return 100.0 * floor_ms / step_ms
