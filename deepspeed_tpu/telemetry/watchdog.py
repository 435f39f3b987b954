"""Recompile watchdog: every XLA compilation becomes a telemetry event.

The framework's performance contracts are compilation contracts: the serving
engine's headline invariant is "admission never recompiles decode" (ONE
decode program per engine lifetime), the train step compiles once per batch
shape, prefill once per bucket. Before this module those invariants were
asserted in tests and silently violable in production — a sharding drift or
a weak-type mismatch recompiles a 30s program mid-traffic and the only
symptom is a latency spike.

``RecompileWatchdog.watch(fn, name, stable=...)`` wraps a jitted callable.
Each call compares the jit cache size before/after (``fn._cache_size()``;
falls back to abstract-signature tracking where unavailable): growth means
this call compiled. Each compilation is recorded with

  * the abstract shape signature of the call's arguments (``f32[8,128]``
    style, long pytrees elided),
  * the compile wall time (the compiling call's wall time minus nothing —
    it includes the first execution, which on TPU is noise next to the
    compile itself) and what it was made of: ``trace_s``, ``lower_s``,
    ``backend_s`` (the thread's ``xla/trace`` / ``xla/lower`` / ``xla/compile``
    spans inside the call, from ``tracing.xla_totals()`` read before and after
    it; ``compile_s`` less the three is the first execution and this proxy) and
    ``cache``, what the persistent cache did for the call's compiles:
    ``written`` if it wrote any, else ``hit`` if it loaded any, else
    ``not_kept`` (None: the call reached no backend compile),
  * registry counters ``compile/<name>`` and histogram ``compile/wall_s``,
  * a JSONL event ``{"type": "compile", "name", "signature", "compile_s",
    "trace_s", "lower_s", "backend_s", "cache", "n_for_name"}``.

A path declared ``stable=True`` may compile ONCE; the second compilation
triggers the watchdog's ``mode``: ``"warn"`` logs loudly, ``"raise"`` throws
``RecompileError`` (the guard a production serving deployment wants — better
a refused request than a silently 100x-slower decode path), ``"off"`` only
records. In raise mode, shape/dtype drift is caught by an abstract-signature
check BEFORE the call executes, so donated operands (the serving KV cache)
survive; drift the signature can't see (sharding/committed-ness) is detected
after the violating call, whose donated inputs are then already consumed.
"""

from __future__ import annotations

import time
from typing import Optional

from ..utils.logging import logger
from .registry import MetricsRegistry, get_registry
from .tracing import XLA_TOTALS, xla_totals

_MAX_SIG_LEAVES = 8


class RecompileError(RuntimeError):
    """A compile-stable path compiled more than once."""


def abstract_signature(args, kwargs=None, limit: int | None = _MAX_SIG_LEAVES) -> str:
    """dtype[shape] summary of a call's arguments; ``limit`` elides long
    pytrees for display."""
    import jax
    import jax.numpy as jnp

    leaves = jax.tree.leaves((args, kwargs or {}))
    shown = leaves if limit is None else leaves[:limit]
    parts = []
    for leaf in shown:
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            try:
                dt = jnp.dtype(leaf.dtype).name
            except TypeError:
                dt = str(leaf.dtype)
            parts.append(f"{dt}[{','.join(map(str, leaf.shape))}]")
        else:
            parts.append(type(leaf).__name__)
    if len(leaves) > len(shown):
        parts.append(f"...+{len(leaves) - len(shown)} leaves")
    return "(" + ", ".join(parts) + ")"


def abstract_key(args, kwargs=None) -> tuple:
    """Full-fidelity hashable key over every leaf's (shape, dtype) — the
    drift check's membership key (a drifted operand may sit past any display
    cutoff, e.g. behind a large params tree). Tuple-of-tuples, no string
    formatting: cheap enough to compute per decode step in raise mode."""
    import jax

    return tuple(
        (tuple(leaf.shape), str(leaf.dtype))
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype")
        else (type(leaf).__name__,)
        for leaf in jax.tree.leaves((args, kwargs or {}))
    )


class RecompileWatchdog:
    def __init__(self, registry: Optional[MetricsRegistry] = None, sink=None,
                 mode: str = "warn", ledger=None):
        if mode not in ("off", "warn", "raise"):
            raise ValueError(f"watchdog mode must be off|warn|raise, got {mode!r}")
        self.registry = registry if registry is not None else get_registry()
        self.sink = sink
        self.mode = mode
        # optional ProgramLedger (telemetry/program_ledger.py): every
        # detected compilation is offered to it for cost-model capture —
        # spec extraction only on this path; the XLA analysis is lazy
        self.ledger = ledger
        self.events: list[dict] = []  # chronological compile events
        self._watched: dict[str, dict] = {}  # name -> {stable, compiles}
        # optional incident hook: called as on_refusal(name, signature) on
        # each FIRST refusal of a stable path (the serving engine points
        # this at its IncidentRecorder — telemetry/incident.py)
        self.on_refusal = None

    # -- bookkeeping ----------------------------------------------------

    def _record(self, name: str, signature: str, compile_s: float,
                key: tuple | None = None, xla: tuple = (0.0, 0.0, 0.0, 0, 0, 0)) -> dict:
        """``xla``: what ``tracing.xla_totals()`` grew by over the call."""
        entry = self._watched[name]
        entry["compiles"] += 1
        if key is not None:
            entry["sigs"].add(key)
        grew = dict(zip(XLA_TOTALS, xla))
        ev = {
            "type": "compile",
            "name": name,
            "signature": signature,
            "compile_s": compile_s,
            "trace_s": grew["trace_s"],
            "lower_s": grew["lower_s"],
            "backend_s": grew["backend_s"],
            "cache": next((v for v in ("written", "hit", "not_kept") if grew[v]), None),
            "n_for_name": entry["compiles"],
        }
        self.events.append(ev)
        self.registry.counter(f"compile/{name}").inc()
        self.registry.histogram("compile/wall_s").observe(compile_s)
        if self.sink is not None:
            self.sink.emit(ev)
        return ev

    def _record_refusal(self, name: str, signature: str, first: bool) -> None:
        """A pre-execution refusal is NOT a compilation: it gets its own
        event type and counter so the compile table / compile wall-time
        histogram keep reporting exactly what XLA compiled."""
        entry = self._watched[name]
        entry["refusals"] += 1
        self.registry.counter(f"refusal/{name}").inc()
        if first:  # retry storms raise again but don't re-log events
            ev = {
                "type": "refusal",
                "name": name,
                "signature": signature,
                "n_refused": entry["refusals"],
            }
            self.events.append(ev)
            if self.sink is not None:
                self.sink.emit(ev)
            if self.on_refusal is not None:
                self.on_refusal(name, signature)

    def _violation(self, name: str, ev: dict) -> None:
        msg = (
            f"recompile watchdog: compile-stable path {name!r} compiled "
            f"{ev['n_for_name']} times (latest signature {ev['signature']}, "
            f"{ev['compile_s']:.2f}s) — an operand's shape/dtype/sharding "
            "drifted on a path whose contract is ONE program")
        if self.mode == "raise":
            raise RecompileError(msg)
        if self.mode == "warn":
            logger.warning(msg)

    # -- wrapping -------------------------------------------------------

    def unique_name(self, base: str) -> str:
        """First caller gets ``base``; later callers get ``base#2``, ... —
        for engines sharing one watchdog (fleet-level telemetry bundles)."""
        if base not in self._watched:
            return base
        i = 2
        while f"{base}#{i}" in self._watched:
            i += 1
        return f"{base}#{i}"

    def watch(self, fn, name: str, stable: bool = False):
        """Wrap jitted ``fn``; returns a call-transparent proxy that records
        every compilation under ``name``. ``stable=True`` arms the
        one-compile contract."""
        if name in self._watched:
            raise ValueError(f"watchdog already watches a path named {name!r}")
        entry = self._watched[name] = {"stable": stable, "compiles": 0,
                                       "refusals": 0, "sigs": set(),
                                       "refused": set()}
        cache_size = getattr(fn, "_cache_size", None)
        seen_sigs: set[tuple] = set()

        def wrapped(*args, **kwargs):
            if stable and self.mode == "raise" and entry["compiles"] >= 1:
                # pre-execution guard: an abstract-signature drift WILL
                # retrace — raise BEFORE calling so donated operands (e.g.
                # the serving KV cache) survive the refusal. Membership is
                # checked on the FULL-fidelity key (a drifted operand may
                # hide past the display cutoff); refused keys are NEVER
                # admitted to the accepted set, so a caller-side retry of
                # the same drifted call is refused again instead of slipping
                # through and consuming the donation. Drift the key can't
                # see (sharding/committed-ness) still falls through to the
                # post-hoc check below, where the donated inputs of the
                # violating call are already consumed.
                key = abstract_key(args, kwargs)
                if key not in entry["sigs"]:
                    first = key not in entry["refused"]
                    entry["refused"].add(key)
                    sig = abstract_signature(args, kwargs)
                    self._record_refusal(name, sig, first)
                    raise RecompileError(
                        f"recompile watchdog: compile-stable path {name!r} "
                        f"refused before execution — signature {sig} would "
                        f"be compilation #{entry['compiles'] + 1} on a path "
                        "whose contract is ONE program"
                        + ("" if first else " (already-refused signature)"))
            if cache_size is not None:
                before = cache_size()
            xla0 = xla_totals()  # one thread-local read: the listener's sums so far
            t0 = time.perf_counter()
            out = fn(*args, **kwargs)
            dt = time.perf_counter() - t0
            if cache_size is not None:
                compiled = cache_size() > before
            else:  # fallback: a never-seen abstract key means a trace
                key = abstract_key(args, kwargs)
                compiled = key not in seen_sigs
                seen_sigs.add(key)
            # callers timing the wrapped call can exclude the compiling one
            # from their latency histograms (a compile is not a step)
            wrapped.last_call_compiled = compiled
            if compiled:
                ev = self._record(
                    name, abstract_signature(args, kwargs), dt,
                    key=abstract_key(args, kwargs),
                    xla=tuple(b - a for a, b in zip(xla0, xla_totals())))
                if self.ledger is not None:
                    # cost-model capture (telemetry/program_ledger.py):
                    # stores shape/dtype/sharding specs only — donated
                    # operands' avals are still readable here, and the
                    # XLA cost/memory analysis is deferred to table()
                    self.ledger.capture(name, fn, args, kwargs, dt)
                if stable and ev["n_for_name"] > 1:
                    self._violation(name, ev)
            return out

        wrapped.__name__ = getattr(fn, "__name__", "wrapped")
        wrapped._watchdog_name = name
        wrapped._wrapped = fn
        wrapped.last_call_compiled = False
        # keep the jit introspection surface working through the wrapper:
        # compile-count assertions (ServingEngine.compile_counts), HLO wire
        # audits (tests lower().compile().as_text()), AOT workflows
        for attr in ("_cache_size", "lower", "eval_shape", "trace"):
            a = getattr(fn, attr, None)
            if a is not None:
                setattr(wrapped, attr, a)
        return wrapped

    # -- reporting ------------------------------------------------------

    def compile_table(self) -> list[dict]:
        """Per-path summary: [{name, stable, compiles, refusals,
        total_compile_s, trace_s, lower_s, backend_s, cache, signatures}]
        sorted by total compile time (``cache``: each compile's verdict).
        ``refusals`` counts pre-execution raise-mode rejections — calls that
        never reached XLA, kept out of the compile accounting."""
        rows = {}
        for name, entry in self._watched.items():
            rows[name] = {
                "name": name,
                "stable": entry["stable"],
                "compiles": entry["compiles"],
                "refusals": entry["refusals"],
                "total_compile_s": 0.0,
                "trace_s": 0.0,
                "lower_s": 0.0,
                "backend_s": 0.0,
                "cache": [],
                "signatures": [],
            }
        for ev in self.events:
            if ev["type"] != "compile":
                continue
            row = rows[ev["name"]]
            row["total_compile_s"] += ev["compile_s"]
            for part in ("trace_s", "lower_s", "backend_s"):
                row[part] += ev[part]
            row["cache"].append(ev["cache"])
            row["signatures"].append(ev["signature"])
        return sorted(rows.values(), key=lambda r: -r["total_compile_s"])
