"""Median duration of the program's own ``.../prefill`` spans in the window:
one bucketed ``SlotWorker.prefill`` call. The in-program twin of
``prefill_ms_p50``."""
from . import span_ring as R

NAME, UNIT, LAYER = "prefill_prog_ms_p50", "ms", "serving device programs"


def read(ctx):
    spans = R.started_in(R.serve_window(ctx))
    return R.median_ms([call for call, _, _ in R.calls(spans, "prefill")])
