"""The decode program's share of its memory roofline for a model with attention
and short-convolution layers and a routed feed-forward: the least time the chip
needs to move what one decode step must (``conv_cost.decode_min_bytes``: the
matmul weights outside the experts, the experts the step touched, the live
positions of the ATTENTION layers alone, the conv state of the rows it advanced
read and written) over the device's own time a run of the decode program
(``decode_floor.py``). The counts are the medians of the traced calls' own
``cached_tokens``, ``state_bytes`` and ``experts_touched``; a program whose spans
lack any (one without conv layers or routing) gives nothing."""
from .. import conv_cost
from . import decode_floor as F

NAME, UNIT, LAYER = "conv_decode_hbm_floor_pct", "%", "serving device programs"
NEEDS = ("cached_tokens", "state_bytes", "experts_touched", "conv_layers")


def read(ctx):
    if "layer_operators" not in ctx["program"]:
        return None
    calls = F.calls(ctx, NEEDS)
    if not calls:
        return None
    cached, state, touched = (F.median(calls, key) for key in NEEDS[:3])
    need = conv_cost.decode_min_bytes(ctx["program"], cached, state, touched)
    return F.share(ctx, calls, need, live_tokens=cached, state_bytes=state,
                   experts_touched=touched)
