"""Median time of one call into ``SlotWorker.prefill`` (one bucketed prompt;
ends in a token fetch, so device-true), timed by the harness round the call,
window only."""
import numpy as np

from .decode_step_ms_p50 import window_calls

NAME, UNIT, LAYER = "prefill_ms_p50", "ms", "serving device programs"


def read(ctx):
    calls = window_calls(ctx, "prefill")
    return 1e3 * float(np.median(calls)) if calls else None
