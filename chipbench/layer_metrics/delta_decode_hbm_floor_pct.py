"""The decode program's share of its memory roofline for a model with attention
and gated-delta-rule layers and a held share of a routed feed-forward: the least
time the chip needs to move what one decode step must
(``delta_cost.decode_min_bytes``: the matmul weights outside the experts, the held
experts the step touched, the live positions of the ATTENTION layers alone, the
delta state of the rows it advanced read AND written) over the device's own time
a run of the decode program (``decode_floor.py``). The counts are the medians of
the traced calls' own ``cached_tokens``, ``state_bytes`` and ``experts_touched``;
a program whose spans lack any, or ``delta_layers`` (one without the operator),
gives nothing."""
from .. import delta_cost
from . import decode_floor as F

NAME, UNIT, LAYER = "delta_decode_hbm_floor_pct", "%", "serving device programs"
NEEDS = ("cached_tokens", "state_bytes", "experts_touched", "delta_layers")


def read(ctx):
    if "delta_head_dim" not in ctx["program"]:
        return None
    calls = F.calls(ctx, NEEDS)
    if not calls:
        return None
    cached, state, touched = (F.median(calls, key) for key in NEEDS[:3])
    need = delta_cost.decode_min_bytes(ctx["program"], cached, state, touched)
    return F.share(ctx, calls, need, live_tokens=cached, state_bytes=state,
                   experts_touched=touched, state_rows=F.median(calls, "state_rows"))
