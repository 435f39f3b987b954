"""From a profiler trace (.xplane.pb) to numbers: device busy and idle time,
time per device operation, collective time and its exposed part, and idle
gaps attributed to what the host was doing.

``load`` turns the file into plain lists (one list of operations per device,
one list of host spans); everything else is interval arithmetic on those, so
``selftest.py`` can check it on hand-made traces as well as on a recorded one.
Times are seconds on the trace's own clock.
"""

from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field

OP_LINE = "XLA Ops"  # the per-core line of executed HLO operations on a TPU plane
MODULE_LINE = "XLA Modules"  # the programs (jit_decode(<hash>), ...) those operations belong to
COLLECTIVE = re.compile(
    r"^(?:[^/]*/)?%?(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\b")
WINDOW_SPAN = "chipbench.window"


@dataclass
class Trace:
    devices: dict = field(default_factory=dict)  # device name -> [(op name, start, end)]
    host: list = field(default_factory=list)  # [(span name, start, end)]
    # op name -> the full HLO text the trace shows for it (where it shows more)
    text: dict = field(default_factory=dict)


def _short(text: str) -> str:
    """'%while.5 = (s32[], ...) while(...)' -> 'while.5': a TPU trace names an
    operation by its whole HLO instruction."""
    return text.split(" = ", 1)[0].lstrip("%")


def _module_of(modules: list, t: float) -> str:
    """Name of the program running at t, without its hash: 'jit_decode'."""
    for name, a, b in modules:
        if a <= t < b:
            return name.split("(", 1)[0]
    return "?"


def load(path: str) -> Trace:
    """Read an .xplane.pb with nothing but jax. Device planes are
    ``/device:TPU:n`` and their operations sit on the ``XLA Ops`` line. A CPU
    trace has no device plane: there the events that carry an ``hlo_op`` stat
    stand for one device, so the rehearsal drives the same code."""
    from jax.profiler import ProfileData

    trace = Trace()
    cpu_ops = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            # only operations: the other lines (modules, steps) cover them.
            # An operation is named "<program>/<instruction>", because two
            # programs number their instructions alike.
            span = lambda ev: (ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
            modules = [(ev.name, *span(ev)) for line in plane.lines
                       if line.name == MODULE_LINE for ev in line.events]
            ops = []
            for line in plane.lines:
                if line.name != OP_LINE:
                    continue
                for ev in line.events:
                    a, b = span(ev)
                    name = f"{_module_of(modules, a)}/{_short(ev.name)}"
                    trace.text.setdefault(name, ev.name)
                    ops.append((name, a, b))
            if ops:
                trace.devices[plane.name] = sorted(ops, key=lambda op: (op[1], -op[2]))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    t0 = ev.start_ns * 1e-9
                    span = (ev.name, t0, t0 + ev.duration_ns * 1e-9)
                    if any(key == "hlo_op" for key, _ in ev.stats):
                        cpu_ops.append(span)
                    elif ev.duration_ns > 0:
                        trace.host.append(span)
    if not trace.devices and cpu_ops:
        trace.devices["/host:CPU (ops)"] = sorted(cpu_ops, key=lambda op: op[1])
    trace.host.sort(key=lambda s: s[1])
    return trace


# -- interval arithmetic ----------------------------------------------------

def merge(intervals) -> list:
    """Union of [start, end) intervals as a sorted list of disjoint ones."""
    out = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(merged) -> float:
    return sum(b - a for a, b in merged)


def clip(merged, lo: float, hi: float) -> list:
    return [(max(a, lo), min(b, hi)) for a, b in merged if min(b, hi) > max(a, lo)]


def subtract(merged_a, merged_b) -> list:
    """The part of the disjoint sorted intervals a that no interval of b covers."""
    out, j = [], 0
    for a, b in merged_a:
        cur = a
        while j < len(merged_b) and merged_b[j][1] <= cur:
            j += 1
        k = j
        while k < len(merged_b) and merged_b[k][0] < b:
            if merged_b[k][0] > cur:
                out.append((cur, merged_b[k][0]))
            cur = max(cur, merged_b[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


# -- reductions ---------------------------------------------------------------

def window_of(trace: Trace) -> tuple:
    """The traced window: the benchmark's own ``chipbench.window`` span where
    the trace has it, else from the first device operation to the last."""
    for name, a, b in trace.host:
        if name == WINDOW_SPAN:
            return (a, b)
    starts = [ops[0][1] for ops in trace.devices.values()]
    ends = [max(op[2] for op in ops) for ops in trace.devices.values()]
    return (min(starts), max(ends))


def collective_intervals(ops) -> list:
    """Intervals during which a collective is in flight on one device: a
    synchronous collective's own interval, and for an asynchronous one from
    its ``-start`` to the end of its ``-done`` (matched first in, first out
    per kind)."""
    out, pending = [], defaultdict(list)
    for name, a, b in ops:
        m = COLLECTIVE.match(name)
        if not m:
            continue
        kind, phase = m.group(1), m.group(2)
        if phase == "-start":
            pending[kind].append(a)
        elif phase == "-done" and pending[kind]:
            out.append((pending[kind].pop(0), b))
        else:
            out.append((a, b))
    return out


def reduce(trace: Trace, span_names=()) -> dict:
    """Everything the layer metrics and ``breakdown`` read. ``span_names``
    are the host spans a gap may be attributed to (the innermost one open at
    the gap's middle)."""
    lo, hi = window_of(trace)
    window = hi - lo
    per_device, op_seconds = {}, defaultdict(float)
    for dev, ops in trace.devices.items():
        segments = leaf_segments(ops, lo, hi)
        busy = merge((a, b) for _, a, b in segments)
        compute = merge((a, b) for name, a, b in segments if not COLLECTIVE.match(name))
        coll = clip(merge(collective_intervals(ops)), lo, hi)
        per_device[dev] = {
            "busy_s": length(busy),
            "collective_s": length(coll),
            "collective_exposed_s": length(subtract(coll, compute)),
            "gaps": subtract([(lo, hi)], busy),
        }
        for name, a, b in segments:
            op_seconds[name] += b - a
    n = max(len(per_device), 1)
    worst = min(per_device, key=lambda d: per_device[d]["busy_s"]) if per_device else None
    spans = [s for s in trace.host if s[0] in set(span_names)]
    gap_by_span, longest = defaultdict(float), []
    for a, b in (per_device[worst]["gaps"] if worst else []):
        mid = 0.5 * (a + b)
        open_spans = [s for s in spans if s[1] <= mid < s[2]]
        owner = max(open_spans, key=lambda s: s[1])[0] if open_spans else "(no span)"
        gap_by_span[owner] += b - a
        longest.append((owner, b - a))
    return {
        "window_s": window,
        "devices": len(per_device),
        "busy_s_mean": sum(d["busy_s"] for d in per_device.values()) / n,
        "busy_s_worst": per_device[worst]["busy_s"] if worst else 0.0,
        "collective_s_mean": sum(d["collective_s"] for d in per_device.values()) / n,
        "collective_exposed_s_mean":
            sum(d["collective_exposed_s"] for d in per_device.values()) / n,
        # self time (an operation's own, without the operations nested in it,
        # as a loop's body is), summed over devices, then per device:
        # comparable with window_s
        "op_seconds": {k: v / n for k, v in op_seconds.items()},
        "op_text": {k: trace.text.get(k, k) for k in op_seconds},
        "idle_by_span": dict(gap_by_span),
        "longest_gaps": sorted(longest, key=lambda g: -g[1])[:5],
    }


def leaf_segments(ops, lo: float, hi: float) -> list:
    """Cut one device's operations into disjoint (name, start, end) segments
    inside [lo, hi), each named by the INNERMOST operation running: the trace
    nests a loop's body inside its ``while``, so an operation is charged only
    what the operations inside it leave. ``ops`` sorted by start, longer
    first."""
    out, stack = [], []  # stack of [name, end, cursor]

    def emit(name, a, b):
        if b > a:
            out.append((name, a, b))

    def pop():
        name, end, cursor = stack.pop()
        emit(name, cursor, end)
        if stack:
            stack[-1][2] = max(stack[-1][2], end)

    for name, a, b in ops:
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        while stack and stack[-1][1] <= a:
            pop()
        if stack:
            emit(stack[-1][0], stack[-1][2], a)
            stack[-1][2] = a
            b = min(b, stack[-1][1])
        stack.append([name, b, a])
    while stack:
        pop()
    return out


def op_seconds_matching(reduced: dict, pattern: str) -> float:
    """Summed per-device seconds of the operations whose name, or whose full
    HLO text in the trace, matches."""
    rx = re.compile(pattern)
    return sum(v for k, v in reduced["op_seconds"].items()
               if rx.search(k) or rx.search(reduced["op_text"].get(k, "")))


def _labelled(name: str, text: str) -> str:
    """'jit_decode/copy.83' + its result type from the HLO text, so that a
    reader of the ledger can tell a whole-cache copy from a bias add."""
    if " = " not in text:
        return name
    return f"{name} {text.split(' = ', 1)[1].split('{', 1)[0].strip()[:48]}"


def breakdown(reduced: dict) -> dict:
    top = sorted(reduced["op_seconds"].items(), key=lambda kv: -kv[1])[:10]
    top = [(_labelled(k, reduced["op_text"].get(k, k)), v) for k, v in top]
    gaps = sorted(reduced["idle_by_span"].items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in top], "idle_gaps": [[k, v] for k, v in gaps]}
