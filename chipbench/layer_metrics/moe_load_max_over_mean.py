"""Median over the window's ``.../decode`` spans of their
``expert_load_max_over_mean``: in the worst routed layer of a decode step, the
live rows sent to the busiest expert over those sent to the mean expert. How
uneven the routing was that the step times were taken under (1 = even; the
weights are random, so it says nothing about a trained router). Absent for a
dense model, and for a program whose spans do not carry the attribute."""
import numpy as np

from . import span_ring as R

NAME, UNIT, LAYER = "moe_load_max_over_mean", "ratio", "model"
ATTR = "expert_load_max_over_mean"


def read(ctx):
    spans = R.started_in(R.serve_window(ctx))
    loads = [call.attrs[ATTR] for call, _, _ in R.calls(spans, "decode") if ATTR in call.attrs]
    return float(np.median(loads)) if loads else None
