"""The decode program's share of its memory roofline for a model with window and
whole-context layers that holds a share of its experts: the least time the chip
needs to read what one decode step must (``kinds_cost.decode_min_bytes``: the
matmul weights outside the experts, the held experts the step touched, the live
positions of the whole-context layers and the rings' of the window layers) over
the device's own time a run of the decode program (``decode_floor.py``). The
counts are the medians of the traced calls' own ``cached_tokens``,
``ring_tokens`` and ``experts_touched``; a program whose spans lack any (one
without rings or routing) gives nothing."""
from .. import kinds_cost
from . import decode_floor as F

NAME, UNIT, LAYER = "kinds_decode_hbm_floor_pct", "%", "serving device programs"
NEEDS = ("cached_tokens", "ring_tokens", "experts_touched")


def read(ctx):
    if "local_attn_layers" not in ctx["program"]:
        return None
    calls = F.calls(ctx, NEEDS)
    if not calls:
        return None
    cached, ring, touched = (F.median(calls, key) for key in NEEDS)
    need = kinds_cost.decode_min_bytes(ctx["program"], cached, ring, touched)
    return F.share(ctx, calls, need, live_tokens=cached, ring_tokens=ring,
                   experts_touched=touched)
