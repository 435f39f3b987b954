"""The decode program's share of its memory roofline: the least time the chip
needs to read what one decode step must (``moe_cost.decode_min_bytes``: the
matmul weights outside the experts, the experts the step touched, the live keys
and values, in the compute dtype) over the device's own time a run of the decode
program (``decode_floor.py``: the traced window's, as ``decode_device_ms_mean``
reads it). The experts touched are the median of the traced calls'
``experts_touched``; the live tokens the median over the traced steps. A routed
model whose spans lack the attribute (a program from before it) gives nothing."""
from .. import moe_cost
from . import decode_floor as F

NAME, UNIT, LAYER = "decode_hbm_floor_pct", "%", "serving device programs"


def read(ctx):
    calls = F.calls(ctx)
    live = F.live_tokens(ctx) if calls else None
    if live is None:
        return None
    touched = F.median(calls, "experts_touched")
    try:
        need = moe_cost.decode_min_bytes(ctx["program"], live, touched)
    except ValueError:
        return None
    return F.share(ctx, calls, need, live_tokens=live, experts_touched=touched)
