"""What the readers of the program's own spans share (no metric of its own).

The program keeps every ended span in one ring
(``deepspeed_tpu.telemetry.tracing.spans``): ``id``, ``parent``, ``path``,
``t0``/``t1`` in ``time.perf_counter()`` seconds, ``attrs``. The readers cut
it to the host window the outside-timed readers use and find a span by the
TAIL of its path (``.../decode``, ``.../decode/dispatch``): a worker call made
outside ``step()`` has a shorter path. A worker call that compiled is left
out. A program without the ring (before PR 24) gives every reader nothing to
read: ``None``, the metric is left out of the line.
"""
import numpy as np

WORKER_CALLS = ("prefill", "chunk", "decode", "verify")  # SlotWorker's device programs


def ring(since: float) -> list:
    from deepspeed_tpu.telemetry import tracing

    read = getattr(tracing, "spans", None)
    return sorted(read(since), key=lambda sp: (sp.t0, -sp.t1)) if read else []


def serve_window(ctx, key: str = "window"):
    """(lo, hi) of the serving loop's host window — or of its traced
    sub-window, ``key="traced"`` — in ``perf_counter`` seconds."""
    s = ctx["serve"]
    if not s or s[key][0] is None:
        return None
    return s["epoch"] + s[key][0], s["epoch"] + s[key][1]


def train_window(ctx):
    """First counted step's start to the last one's end."""
    t = ctx["train"]
    if not t or not t["steps"]:
        return None
    t_window = ctx["run"].t_start + ctx["t_setup"]
    return t_window + t["steps"][0][0], t_window + t["steps"][-1][1]


def started_in(window, lead_s: float = 0.0) -> list:
    """The ring's spans that began in the window (or up to ``lead_s`` before)."""
    if window is None:
        return []
    lo, hi = window
    return [sp for sp in ring(lo - lead_s) if sp.t0 < hi]


def is_a(sp, tail: str) -> bool:
    return sp.path == tail or sp.path.endswith("/" + tail)


def calls(spans, *kinds, compiled: bool = False) -> list:
    """(call, dispatch, fetch) of every worker call of these kinds, without
    those that compiled unless asked; ``fetch`` is None for a chunk left
    asynchronous."""
    kids = {}
    for sp in spans:
        if sp.name in ("dispatch", "fetch"):
            kids.setdefault(sp.parent, {})[sp.name] = sp
    return [(sp, kids[sp.id]["dispatch"], kids[sp.id].get("fetch")) for sp in spans
            if any(is_a(sp, k) for k in kinds) and "dispatch" in kids.get(sp.id, {})
            and (compiled or not sp.attrs.get("compiled"))]


def median_ms(spans):
    return 1e3 * float(np.median([sp.t1 - sp.t0 for sp in spans])) if spans else None
