"""The decode program's share of its memory roofline for a model whose layer stack
runs several times over the same weights: the least time the chip needs to read
what one decode step must (``loop_cost.decode_min_bytes``: the layers' matmul
weights once a PASS, the head once, the live keys and values of every (pass,
layer), in the compute dtype) over the device's own time a run of the decode
program (``decode_floor.py``), as ``decode_hbm_floor_pct`` is built. The live
tokens are the median over the traced steps. A program without ``layer_passes``,
or whose spans lack it (a system from before the passes), gives nothing."""
from .. import loop_cost
from . import decode_floor as F

NAME, UNIT, LAYER = "loop_decode_hbm_floor_pct", "%", "serving device programs"


def read(ctx):
    if "layer_passes" not in ctx["program"]:
        return None
    calls = F.calls(ctx, ("layer_passes",))
    live = F.live_tokens(ctx) if calls else None
    if live is None:
        return None
    last = calls[-1].attrs
    return F.share(ctx, calls, loop_cost.decode_min_bytes(ctx["program"], live), live_tokens=live,
                   **{key: last.get(key) for key in ("layer_passes", "cache_layers",
                                                     "exit_pass_mean", "exit_cdf")})
