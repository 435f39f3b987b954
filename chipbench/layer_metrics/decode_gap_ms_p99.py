"""The 99th percentile, over the host window, of the time between the ENDS of
consecutive decode steps (``.../decode`` spans, which fetch and so end when the
device is done): how long every decoding request waits for its next token at worst.
What chunked admission bounds and nothing else read: with whole-prompt prefill a
16,384-row program stands between two steps; with chunks of 2,048 rows at most
``chunks_per_step`` of them do. Absent where the window holds fewer than three
steps."""
import numpy as np

from . import span_ring as R

NAME, UNIT, LAYER = "decode_gap_ms_p99", "ms", "serving scheduler"


def read(ctx):
    if not ctx["serve"]:
        return None
    found = R.calls(R.started_in(R.serve_window(ctx)), "decode")
    ends = sorted(call.t1 for call, _, _ in found)
    if len(ends) < 3:
        return None
    gaps = 1e3 * np.diff(ends)
    ctx["run"].note(event="decode_gaps", steps=len(ends), p50_ms=float(np.percentile(gaps, 50)),
                    p99_ms=float(np.percentile(gaps, 99)), max_ms=float(np.max(gaps)))
    return float(np.percentile(gaps, 99))
