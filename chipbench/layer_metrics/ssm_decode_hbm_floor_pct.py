"""The decode program's share of its memory roofline for a model that keeps a
recurrent state per sequence beside grouped-query K/V: the least time the chip
needs to move what one decode step must (``ssm_cost.decode_min_bytes``: the
matmul weights once, the state of the rows the step advanced read AND written,
the live K/V at ``num_kv_heads`` heads) over the device's own time a run of the
decode program (``decode_floor.py``). The state's bytes and the live tokens are
the medians of the traced calls' own ``state_bytes`` and ``cached_tokens``; a
program whose spans lack either (one without the mixer) gives nothing."""
from .. import ssm_cost
from . import decode_floor as F

NAME, UNIT, LAYER = "ssm_decode_hbm_floor_pct", "%", "serving device programs"
NEEDS = ("cached_tokens", "state_bytes")


def read(ctx):
    if "ssm_state_size" not in ctx["program"]:
        return None
    calls = F.calls(ctx, NEEDS)
    if not calls:
        return None
    cached, state = (F.median(calls, key) for key in NEEDS)
    need = ssm_cost.decode_min_bytes(ctx["program"], cached, state)
    return F.share(ctx, calls, need, live_tokens=cached, state_bytes=state,
                   state_rows=F.median(calls, "state_rows"))
