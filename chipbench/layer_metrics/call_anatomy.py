"""What the readers of a serving call's anatomy share (no metric of its own).

A worker call (``.../decode``, ``.../prefill``) is one span of the program's
ring with two children and, since PR 35, five grandchildren:
``dispatch`` -> ``operands``, ``key``, ``enqueue``; ``fetch`` -> ``wait``,
``copy``; and two counters on the call itself, ``h2d`` and ``d2h`` (the
separate host->device operands it handed over, the separate arrays it fetched).
Beside them stands what the device did for the call: the trace's own seconds
of the call's program (operations are named ``<program>/<instruction>``:
``jit_decode/...``, ``jit_prefill/...``), clipped to the traced window, over
the calls of that window. A call that straddles an edge of the window counts
by the share of it inside; a call that compiled is left out.

A program without the grandchildren or the counters (before PR 35) gives their
readers nothing to read (``None``); its call spans and the trace are there, so
the device's time a run and the call's overhead read the same on it. A trace
that names no program (the CPU rehearsal's) gives those two nothing either.
"""
from collections import defaultdict

import numpy as np

from . import span_ring as R

PROGRAM = {"decode": "jit_decode", "prefill": "jit_prefill"}  # call kind -> its program's name
PARTS = {"dispatch": ("operands", "key", "enqueue"), "fetch": ("wait", "copy")}
LEAD_S = 5.0  # look this far round a window for a call, and its parts, that straddle an edge


def trees(spans, kind: str) -> list:
    """(call, {its spans below by name}) of every call of this kind that did not
    compile: ``dispatch``, ``fetch`` and whatever of their parts the program has."""
    below = defaultdict(dict)
    for sp in spans:
        below[sp.parent][sp.name] = sp
    out = []
    for call, dispatch, fetch in R.calls(spans, kind):
        parts = {"dispatch": dispatch, **below[dispatch.id]}
        if fetch is not None:
            parts.update(fetch=fetch, **below[fetch.id])
        out.append((call, parts))
    return out


def inside(window, kind: str) -> list:
    """(call, parts, the share of the call inside the window) of the calls that
    overlap it."""
    lo, hi = window
    out = []
    for call, parts in trees(R.started_in((lo, hi + LEAD_S), LEAD_S), kind):
        part = min(call.t1, hi) - max(call.t0, lo)
        if part > 0 and call.t1 > call.t0:
            out.append((call, parts, part / (call.t1 - call.t0)))
    return out


def program_seconds(ctx) -> dict:
    """The trace's device seconds by program (``jit_decode``, ...), or {} where it
    names none."""
    tr = ctx["trace"]
    by = defaultdict(float)
    for name, seconds in (tr["op_seconds"] if tr else {}).items():
        if "/" in name:
            by[name.split("/", 1)[0]] += seconds
    return dict(by)


def traced(ctx, kind: str):
    """Over the traced window: ``runs`` (calls, an edge call by its share),
    ``call_ms`` (their mean duration) and ``device_ms`` (the program's device
    seconds a run; None where the trace names no such program). None where the
    window holds no call."""
    window = R.serve_window(ctx, "traced")
    found = inside(window, kind) if window else []
    runs = sum(w for _, _, w in found)
    if runs <= 0:
        return None
    seconds = program_seconds(ctx).get(PROGRAM[kind])
    return {"runs": runs,
            "call_ms": 1e3 * sum(w * (c.t1 - c.t0) for c, _, w in found) / runs,
            "device_ms": 1e3 * seconds / runs if seconds else None,
            "calls": found}


def device_ms(ctx, kind: str):
    t = traced(ctx, kind)
    return t["device_ms"] if t else None


def overhead_ms(ctx, kind: str):
    t = traced(ctx, kind)
    return t["call_ms"] - t["device_ms"] if t and t["device_ms"] is not None else None


def host_calls(ctx, kind: str) -> list:
    """``trees`` of the calls of this kind that began in the host window."""
    return trees(R.started_in(R.serve_window(ctx)), kind)


def host_part(ctx, kind: str, name: str) -> list:
    """The ``name`` spans (``enqueue``, ``copy``, ...) under those calls."""
    return [parts[name] for _, parts in host_calls(ctx, kind) if name in parts]


def _ms(spans, weights=None):
    if not spans:
        return None
    d = 1e3 * np.asarray([sp.t1 - sp.t0 for sp in spans])
    return {"mean": float(np.average(d, weights=weights)), "p50": float(np.median(d))}


def anatomy(found) -> dict:
    """The table of one call kind in one window, from ``inside``'s triples: mean
    (an edge call by its share) and p50 of the call and of each part, ms; the
    counters' medians."""
    out = {"runs": sum(w for _, _, w in found),
           "call": _ms([c for c, _, _ in found], [w for _, _, w in found])}
    for name in ("dispatch", *PARTS["dispatch"], "fetch", *PARTS["fetch"]):
        have = [(parts[name], w) for _, parts, w in found if name in parts]
        out[name] = _ms([sp for sp, _ in have], [w for _, w in have])
    for counter in ("h2d", "d2h"):
        counts = [c.attrs[counter] for c, _, _ in found if counter in c.attrs]
        out[counter] = float(np.median(counts)) if counts else None
    return out


def note(ctx) -> None:
    """The ``call_anatomy`` line: for ``decode`` and ``prefill``, the traced window
    and the host window side by side (the profiler session is open only in the
    first, and costs the host something: ``overhead_ms``, the call's mean less
    the traced window's device time a run, is in both; where the two windows
    hold another mix of prefill buckets the host one's says little). In the
    traced one also that device time and ``runtime_ms`` = enqueue + wait -
    device (means): what the runtime adds between the hand-over and the
    results being ready.
    ``programs_s``: the trace's device seconds by program beside ``busy_s``, the
    seconds they should add up to."""
    tables = {}
    for kind in PROGRAM:
        t = traced(ctx, kind)
        table = {"traced": None, "host": None}
        device = t["device_ms"] if t else None
        if t:
            a = table["traced"] = anatomy(t["calls"])
            a["device_ms"] = device
            if device is not None and a["enqueue"] and a["wait"]:
                a["runtime_ms"] = a["enqueue"]["mean"] + a["wait"]["mean"] - device
        found = [(c, parts, 1.0) for c, parts in host_calls(ctx, kind)]
        if found:
            table["host"] = anatomy(found)
        for a in table.values():
            if a and device is not None:
                a["overhead_ms"] = a["call"]["mean"] - device
        tables[kind] = table
    if not any(v for table in tables.values() for v in table.values()):
        return
    by = program_seconds(ctx)
    mine = set(PROGRAM.values())
    other = sorted(((k, v) for k, v in by.items() if k not in mine), key=lambda kv: -kv[1])
    tr = ctx["trace"]
    ctx["run"].note(event="call_anatomy", **tables,
                    programs_s={**{k: by.get(k) for k in sorted(mine)},
                                "other": sum(v for _, v in other), "other_top": other[:6]},
                    busy_s=tr["busy_s_worst"] if tr else None,
                    window_s=tr["window_s"] if tr else None)
