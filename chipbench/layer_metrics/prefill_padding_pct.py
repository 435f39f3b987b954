"""100 x (1 - sum of ``true_len`` / sum of ``bucket``) over the window's
``.../prefill`` spans: the share of the prefill work attempted that was
padding."""
from . import span_ring as R

NAME, UNIT, LAYER = "prefill_padding_pct", "%", "serving device programs"


def read(ctx):
    spans = R.started_in(R.serve_window(ctx))
    attrs = [call.attrs for call, _, _ in R.calls(spans, "prefill")]
    if not attrs:
        return None
    return 100.0 * (1.0 - sum(a["true_len"] for a in attrs) / sum(a["bucket"] for a in attrs))
