"""What the ``*decode_hbm_floor_pct`` readers share (no metric of its own).

A decode floor is a share of the DEVICE's time: the least time the chip needs to
move what one decode step must (each reader's own cost function, in bytes) over
the device's own time a run of the decode program (``decode_device_ms_mean``'s
quantity: ``call_anatomy.device_ms``). What the cost counts (live tokens, the
spans' ``experts_touched``, ``state_bytes``, ...) is the median over the calls
and steps of the TRACED window, so numerator and denominator are of the same
steps. Until PR 57 the denominator was the median ``.../decode`` span of the
host window: a program that enqueues a step before it has fetched the last
shortens that span below the device's time, and a sound, faster program read
over 100%. The device's time is bounded by the chip whoever enqueues the work.

A trace that names no program (the CPU's: a rehearsal, which prints no number)
and a hand-made ring with no trace have no such time; there the median span
stands in, over the host window where the run traced none, so that a rehearsal
still drives every cost function and lists the metric. A chip's trace names its
programs: one without the decode program gives nothing.
"""
import numpy as np

from . import call_anatomy as A
from . import span_ring as R


def _window(ctx) -> str:
    return "traced" if (ctx["serve"].get("traced") or (None,))[0] is not None else "window"


def calls(ctx, needs=()) -> list:
    """The decode calls (none that compiled) that began in the window, with every
    attribute in ``needs`` on their span."""
    if not ctx["serve"]:
        return []
    found = R.calls(R.started_in(R.serve_window(ctx, _window(ctx))), "decode")
    return [call for call, _, _ in found if all(key in call.attrs for key in needs)]


def median(spans, key: str):
    have = [sp.attrs[key] for sp in spans if key in sp.attrs]
    return float(np.median(have)) if have else None


def live_tokens(ctx):
    """Median over the window's steps of the cached tokens their decode attended to
    (the harness's own count, ``drivers/serve.py``); None where it holds no step."""
    s = ctx["serve"]
    lo, hi = s[_window(ctx)]
    live = [n for ts, te, _, n in s["steps"] if lo <= ts and te <= hi and n > 0]
    return float(np.median(live)) if live else None


def share(ctx, found, need_bytes: float, **counts):
    """100 x floor / device time, and the ``roofline`` line: ``floor_ms``,
    ``device_ms``, ``bytes``, the counts the cost took, and ``span_ms`` (the median
    call span of the same window, which the share is no longer over)."""
    span_ms = R.median_ms(found)
    if ctx.get("trace") and A.program_seconds(ctx):
        device_ms = A.device_ms(ctx, "decode")
    else:
        device_ms = span_ms  # no program is named: the CPU, whose numbers are never printed
    if not device_ms:
        return None
    floor_ms = 1e3 * need_bytes / ctx["peak"]["hbm_bytes_per_s"]
    ctx["run"].note(event="roofline", program="decode", floor_ms=floor_ms, device_ms=device_ms,
                    span_ms=span_ms, bytes=need_bytes, window=_window(ctx), **counts)
    return 100.0 * floor_ms / device_ms
