"""Median time of one call into ``SlotWorker.decode`` (dispatch to fetched
tokens: the fetch syncs, so this is device-true plus one host round trip),
timed by the harness round the call, window only. Raw samples, not the
engine's log-bucketed ``serving/decode_step_sec`` histogram."""
import numpy as np

NAME, UNIT, LAYER = "decode_step_ms_p50", "ms", "serving device programs"
CALL = "decode"


def window_calls(ctx, call):
    s = ctx["serve"]
    if not s:
        return []
    lo, hi = s["window"]
    return [b - a for a, b in s["calls"].get(call, []) if lo <= a < hi]


def read(ctx):
    calls = window_calls(ctx, CALL)
    return 1e3 * float(np.median(calls)) if calls else None
