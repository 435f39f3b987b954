"""``serve_host_gap_pct`` for a model that generates by diffusion over blocks: the share of
the window in which, by the host's own clock, no serving program was enqueued or running:
100 x the summed gaps from the end of one worker call's ``fetch`` to the end of the next
worker call's ``dispatch``, over the window, the BLOCK STEP among the worker calls
(``block_calls.WORKER_CALLS``; the accepted reader finds no block step and would read the
time between two prefills as a gap). A lower bound of ``device_idle_pct``.

Also writes a ``block_host_gaps`` note: the gap seconds split by the innermost program span
open in them, in the host window and in the traced one. Absent where the window holds no
``.../block_step`` span."""
from collections import defaultdict

from ..reduce import leaf_segments
from . import block_calls as B
from . import span_ring as R

NAME, UNIT, LAYER = "block_host_gap_pct", "%", "serving scheduler"
LEAD_S = 5.0  # look this far before a window for the call whose gap runs into it


def share(window):
    """{pct, gap_s, window_s, by_span} of a window; None if it holds no two worker calls."""
    if window is None:
        return None
    lo, hi = window
    spans = R.started_in(window, LEAD_S)
    worker = R.calls(spans, *B.WORKER_CALLS, compiled=True)  # a compile keeps the device waiting too
    pairs = [(prev[2].t1, nxt[1].t1) for prev, nxt in zip(worker, worker[1:])
             if prev[2] is not None]
    pairs = [(max(a, lo), min(b, hi)) for a, b in pairs if b > lo and a < hi]
    if not pairs:
        return None
    segments = leaf_segments([(sp.name, sp.t0, sp.t1) for sp in spans], lo, hi)
    by_span, i = defaultdict(float), 0
    for a, b in pairs:
        while i < len(segments) and segments[i][2] <= a:
            i += 1
        j, covered = i, 0.0
        while j < len(segments) and segments[j][1] < b:
            name, s, e = segments[j]
            part = min(e, b) - max(s, a)
            by_span[name] += part
            covered += part
            j += 1
        by_span["outside"] += (b - a) - covered
    total = sum(b - a for a, b in pairs)
    return {"pct": 100.0 * total / (hi - lo), "gap_s": total, "window_s": hi - lo,
            "by_span": dict(by_span)}


def read(ctx):
    if not B.calls(ctx, "window"):
        return None
    whole = share(R.serve_window(ctx))
    if whole is None:
        return None
    ctx["run"].note(event="block_host_gaps", window=whole,
                    traced=share(R.serve_window(ctx, "traced")))
    return whole["pct"]
