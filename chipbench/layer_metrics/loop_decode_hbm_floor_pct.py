"""The decode program's share of its memory roofline for a model whose layer stack
runs several times over the same weights: the least time the chip needs to read
what one decode step must (``loop_cost.decode_min_bytes``: the layers' matmul
weights once a PASS, the head once, the live keys and values of every (pass,
layer), in the compute dtype) over the median ``.../decode`` span, as
``decode_hbm_floor_pct`` is built. The live tokens are the median over the window's
steps. A program without ``layer_passes``, or whose spans lack it (a system from
before the passes), gives nothing."""
import numpy as np

from .. import loop_cost
from . import span_ring as R

NAME, UNIT, LAYER = "loop_decode_hbm_floor_pct", "%", "serving device programs"


def read(ctx):
    s = ctx["serve"]
    if not s or "layer_passes" not in ctx["program"]:
        return None
    calls = [call for call, _, _ in R.calls(R.started_in(R.serve_window(ctx)), "decode")]
    calls = [c for c in calls if "layer_passes" in c.attrs]
    lo, hi = s["window"]
    live = [n for ts, te, _, n in s["steps"] if lo <= ts and te <= hi and n > 0]
    if not calls or not live:
        return None
    need = loop_cost.decode_min_bytes(ctx["program"], float(np.median(live)))
    floor_ms = 1e3 * need / ctx["peak"]["hbm_bytes_per_s"]
    step_ms = R.median_ms(calls)
    ctx["run"].note(event="roofline", program="decode", floor_ms=floor_ms, step_ms=step_ms,
                    bytes=need, live_tokens=float(np.median(live)),
                    layer_passes=calls[-1].attrs["layer_passes"],
                    cache_layers=calls[-1].attrs.get("cache_layers"),
                    exit_pass_mean=calls[-1].attrs.get("exit_pass_mean"),
                    exit_cdf=calls[-1].attrs.get("exit_cdf"))
    return 100.0 * floor_ms / step_ms
