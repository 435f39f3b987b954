"""The decode program's share of its memory roofline for a model that keeps a
recurrent state per sequence beside grouped-query K/V: the least time the chip
needs to move what one decode step must (``ssm_cost.decode_min_bytes``: the
matmul weights once, the state of the rows the step advanced read AND written,
the live K/V at ``num_kv_heads`` heads) over the median ``.../decode`` span. The
state's bytes and the live tokens are the medians of the spans' own
``state_bytes`` and ``cached_tokens``; a program whose spans lack either (one
without the mixer) gives nothing."""
import numpy as np

from .. import ssm_cost
from . import span_ring as R

NAME, UNIT, LAYER = "ssm_decode_hbm_floor_pct", "%", "serving device programs"


def read(ctx):
    if not ctx["serve"] or "ssm_state_size" not in ctx["program"]:
        return None
    calls = [call for call, _, _ in R.calls(R.started_in(R.serve_window(ctx)), "decode")]
    calls = [c for c in calls if "state_bytes" in c.attrs and "cached_tokens" in c.attrs]
    if not calls:
        return None
    state = float(np.median([c.attrs["state_bytes"] for c in calls]))
    cached = float(np.median([c.attrs["cached_tokens"] for c in calls]))
    need = ssm_cost.decode_min_bytes(ctx["program"], cached, state)
    floor_ms = 1e3 * need / ctx["peak"]["hbm_bytes_per_s"]
    step_ms = R.median_ms(calls)
    ctx["run"].note(event="roofline", program="decode", floor_ms=floor_ms, step_ms=step_ms,
                    bytes=need, state_bytes=state, cached_tokens=cached,
                    state_rows=float(np.median([c.attrs.get("state_rows", 0) for c in calls])))
    return 100.0 * floor_ms / step_ms
