"""What the readers of set-up's anatomy share (no metric of its own).

The program keeps what starting up was made of in spans that outlive its ring
(``deepspeed_tpu.telemetry.tracing``, PR 54): ``startup/build`` with the
engine's phases under it, the worker calls and train steps that compiled, and
one ``xla/trace`` / ``xla/lower`` / ``xla/compile`` for every outermost trace,
lowering and backend compile (or cache load) of the process, whoever asked for
it: ``program`` is jax's name for the function, ``cache`` on a compile is
``hit`` / ``written`` / ``not_kept``, and the path says under which span it
happened (none: a program of the harness's own, the check's probe and
references). ``tracing.spans()`` returns them with the ring's.

The readers take the spans that ENDED in set-up, ``[run.t_start, run.t_start +
t_setup)``. The three kinds of ``xla/*`` span are disjoint on a thread, so their
sum is compilation's share of set-up; ``startup/build`` is a phase that
contains some of them. A program without the spans (before PR 54) gives every
reader nothing to read: ``None``.
"""
from collections import defaultdict

from . import span_ring as R

BUILD = "startup/build"
XLA = ("xla/trace", "xla/lower", "xla/compile")


def in_setup(ctx) -> list:
    lo = ctx["run"].t_start
    hi = lo + ctx["t_setup"]
    return [sp for sp in R.ring(float("-inf")) if lo <= sp.t1 < hi]


def xla(ctx, *kinds, cache=None):
    """Set-up's ``xla/<kind>`` spans (a compile's by its cache verdict), or None
    where the program has no such span at all."""
    found = [sp for sp in in_setup(ctx) if sp.name in XLA]
    if not found:
        return None
    return [sp for sp in found if sp.name[4:] in kinds
            and (cache is None or sp.attrs.get("cache") in cache)]


def seconds(spans):
    return None if spans is None else sum(sp.t1 - sp.t0 for sp in spans)


def origin(sp) -> str:
    """Whose program an ``xla/*`` span was, by the span it happened under: the
    build's (``startup``), a worker call's or a train step's first run
    (``calls``), none (``harness``: the check and the warm-up's own programs)
    or any other span of the program's (``other``)."""
    under = sp.path[:-len(sp.name)].rstrip("/")
    if not under:
        return "harness"
    if under.startswith("startup"):
        return "startup"
    if under.startswith("train/train_batch") or set(under.split("/")) & set(R.WORKER_CALLS):
        return "calls"
    return "other"


def _union_s(spans) -> float:
    total, end = 0.0, float("-inf")
    for sp in sorted(spans, key=lambda sp: sp.t0):
        total += max(0.0, sp.t1 - max(sp.t0, end))
        end = max(end, sp.t1)
    return total


def note(ctx) -> None:
    """The ``setup_anatomy`` line: where set-up's seconds went, by the program's
    own spans."""
    spans = in_setup(ctx)
    kept = [sp for sp in spans if sp.name in XLA or getattr(sp, "keep", True)]
    found = [sp for sp in kept if sp.name in XLA]
    if not found:
        return
    from deepspeed_tpu.telemetry import tracing

    by_origin = defaultdict(lambda: {"trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0,
                                     "load_s": 0.0, "spans": 0})
    verdicts = {v: [sp for sp in found if sp.attrs.get("cache") == v]
                for v in ("hit", "written", "not_kept")}
    for sp in found:
        kind, verdict = sp.name[4:], sp.attrs.get("cache")
        part = {"trace": "trace_s", "lower": "lower_s"}.get(
            kind, "load_s" if verdict == "hit" else "compile_s")
        row = by_origin[origin(sp)]
        row[part] += sp.t1 - sp.t0
        row["spans"] += 1
    # the per-phase and per-program sums are the program's own (its ``startup`` table)
    phases, programs = tracing.sum_kept(kept)
    first_calls = defaultdict(lambda: {"n": 0, "s": 0.0})
    for sp in kept:
        if sp.name not in XLA and not sp.path.startswith(BUILD) and (
                sp.attrs.get("compiled") or sp.path == "train/train_batch"):
            first_calls[sp.name]["n"] += 1
            first_calls[sp.name]["s"] += sp.t1 - sp.t0
    stats = getattr(tracing, "kept_stats", dict)()
    ctx["run"].note(
        event="setup_anatomy", setup_s=ctx["t_setup"],
        first_span_s=min(sp.t0 for sp in spans) - ctx["run"].t_start,
        phases=phases, first_calls=dict(first_calls), xla=dict(by_origin),
        cache={v: {"n": len(sps), "s": seconds(sps)} for v, sps in verdicts.items()},
        top=[{"program": r["program"], "under": r["under"],
              "s": r["trace_s"] + r["lower_s"] + r["compile_s"] + r["load_s"],
              "cache": [v for v in verdicts if r[v]]} for r in programs[:10]],
        unaccounted_s=ctx["t_setup"] - _union_s(kept),
        kept=stats.get("kept"), dropped=stats.get("dropped"), ring=len(tracing.spans()))
