"""Median duration of the program's own ``.../decode`` spans in the window:
one call into ``SlotWorker.decode``, dispatch to fetched tokens, timed where
the work happens. The in-program twin of ``decode_step_ms_p50``."""
from . import span_ring as R

NAME, UNIT, LAYER = "decode_prog_ms_p50", "ms", "serving device programs"


def read(ctx):
    spans = R.started_in(R.serve_window(ctx))
    return R.median_ms([call for call, _, _ in R.calls(spans, "decode")])
