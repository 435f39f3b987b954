"""Median duration of ``.../decode/dispatch`` in the window: the host time
from entering ``SlotWorker.decode`` until the jitted call has returned (key
split, operand conversion and upload, enqueue), before which the device
cannot start the step."""
from . import span_ring as R

NAME, UNIT, LAYER = "decode_dispatch_ms_p50", "ms", "serving device programs"


def read(ctx):
    spans = R.started_in(R.serve_window(ctx))
    return R.median_ms([dispatch for _, dispatch, _ in R.calls(spans, "decode")])
