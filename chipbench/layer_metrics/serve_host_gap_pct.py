"""The share of the window in which, by the host's own clock, no serving
program was enqueued or running: 100 x the summed gaps from the end of one
worker call's ``fetch`` (the device has finished) to the end of the next
worker call's ``dispatch`` (the device can start again), over the window. A
lower bound of ``device_idle_pct``: launch and copy-back latency inside
``dispatch`` and ``fetch`` is idle device time no host code can remove.

Also writes a ``host_gaps`` note: the gap seconds split by the innermost
program span open in them (``emit``, ``sweep``, ``admit``, ``dispatch``, ...;
``outside``: no program span, i.e. the harness's stamping and generator), and
the same share over the traced sub-window beside that window's
``device_idle_pct`` (and the decode spans' medians in both windows: the
profiler session is open only in the traced one)."""
from collections import defaultdict

from ..reduce import leaf_segments
from . import device_idle_pct
from . import span_ring as R

NAME, UNIT, LAYER = "serve_host_gap_pct", "%", "serving scheduler"
LEAD_S = 5.0  # look this far before a window for the call whose gap runs into it


def gaps(window):
    """(gap seconds in the window, the same by innermost open span); None if
    the window holds no two worker calls."""
    lo, hi = window
    spans = R.started_in(window, LEAD_S)
    worker = R.calls(spans, *R.WORKER_CALLS, compiled=True)  # a compile keeps the device waiting too
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
    return sum(b - a for a, b in pairs), dict(by_span)


def share(window):
    found = gaps(window) if window else None
    if found is None:
        return None
    total, by_span = found
    decodes = R.calls(R.started_in(window), "decode")
    return {"pct": 100.0 * total / (window[1] - window[0]), "gap_s": total,
            "window_s": window[1] - window[0], "by_span": by_span,
            # beside each other in the two windows: what an open profiler session costs
            "decode_ms_p50": R.median_ms([call for call, _, _ in decodes]),
            "decode_dispatch_ms_p50": R.median_ms([disp for _, disp, _ in decodes])}


def read(ctx):
    whole = share(R.serve_window(ctx))
    if whole is None:
        return None
    traced = share(R.serve_window(ctx, "traced"))
    if traced is not None:
        traced["device_idle_pct"] = device_idle_pct.read(ctx)
    ctx["run"].note(event="host_gaps", window=whole, traced=traced)
    return whole["pct"]
