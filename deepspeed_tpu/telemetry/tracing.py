"""The program's one span system: nested host spans, kept in a ring, on the
clock everything else uses.

A span is a named wall-clock region of host code. Spans nest by THREAD, not
by tracer: there is one stack a thread for every ``SpanTracer`` of the
process and for the module's own ``span(...)`` (a default tracer, for code
that runs before any ``Telemetry`` exists: an engine's build), so a span is
the parent of whatever opens under it on its thread, whoever holds the
tracer. Each span:

  * opens a ``jax.profiler.TraceAnnotation`` under its path, so with a
    profiler session open the same region is on the device trace's clock
    (TensorBoard / Perfetto) — the NVTX role the reference's
    ``instrument_w_nvtx`` plays (utils/nvtx.py);
  * when it ends — normally or by an exception — is appended to ONE bounded,
    process-wide ring (``RING_CAPACITY`` records, O(1), no I/O) as the
    ``Span`` itself: ``id``, ``parent`` (id of the span open on this thread
    when it began, or None), ``path`` (the slash-joined nesting,
    ``serve/step/decode``), ``t0``/``t1`` in absolute
    ``time.perf_counter()`` seconds, its attributes, and the ``replica_id``
    of the engine that owns it (inherited from the enclosing span; several
    replicas can share a process). ``spans(since)`` / ``clear_spans()`` are
    the read surface that ``telemetry_snapshot()``, the benchmark's readers
    and the tests share;
  * optionally emits a JSONL event ``{"type": "span", "name", "path",
    "depth", "id", "parent", "start_s", "dur_s"}`` (``start_s`` on the same
    ``perf_counter`` clock, ``t`` absolute wall time added by the exporter).

Spans that outlive the ring. A span opened with ``keep=True``, and every span
that opens inside it, goes, beside the ring, into ONE bounded process-wide list
that no later span evicts. ``keep`` can also be set on an OPEN span (a worker
call learns that it compiled only once its program has returned): that keeps
the span alone, since what ended inside it is in the ring already and a kept
half-subtree would name parents that are not kept. The list is bounded
(``KEPT_CAPACITY``; past it a span is dropped and counted, ``kept_stats()``,
so a recompile storm cannot grow it). ``spans(since)`` returns the kept spans
with the ring's, once each, in the order they ended. What starts a process is
kept (``startup/build`` and its phases, the calls that compiled) and no steady
span is: the hot path pays the parent's flag read on entry and one test on
exit.

Every trace, lowering and compile of the process is a span too: ``xla/trace``,
``xla/lower``, ``xla/compile``, made by ONE listener on jax's own monitoring
events (installed when this module is imported; ``listen(False)`` takes it off
again) from the event that begins each and the duration that ends it, ``t0`` /
``t1`` taken on ``time.perf_counter()`` at the two. An ``xla/*`` span's
``parent`` may name a span that is not kept (a first call's ``enqueue``):
its ``path`` still says where it happened. jax fires the trace event for every inner
``jit`` traced inside an outer one, so only the OUTERMOST of the three kinds on
a thread becomes a span (``inner`` counts the rest): spans of the three kinds
are disjoint on a thread and their sum counts no second twice. ``parent`` /
``path`` come from the span open on the compiling thread
(``startup/build/draw/xla/compile``; none: a program of the caller's own);
``program`` is jax's ``fun_name``; an ``xla/compile`` also says what the
persistent cache did, ``cache``: ``hit`` (then ``load_s``, ``saved_s``),
``written`` (compiled, and the cache kept it) or ``not_kept`` (compiled, and
nothing was written: under the cache's floors, or no cache, so EVERY process
pays it again). They are ended, kept spans with no JSONL event and the one kind
with no ``TraceAnnotation``: the profiler is not running during set-up, and
where it is, jax's own compile events are on its trace. ``xla_totals()`` are
the thread's running sums, which the recompile watchdog brackets a call with.

``time.perf_counter()`` is the clock of ``RequestResult``,
``ServingEngine.set_epoch`` and the benchmark harness; ``RequestTracer``'s
events are seconds since the engine's epoch, which ``telemetry_snapshot()``
states, so one addition puts them beside the spans.

Device-accurate mode: dispatch is async under JAX, so a span that merely
brackets a ``jit`` call times the *dispatch*. Instrumented code attaches the
step's output via ``span.set_sync(x)`` (or the ``sync=`` argument); a tracer
built with ``device_sync=True`` then blocks on it at exit via
``jax.block_until_ready`` — the CUDA-event analogue on TPU. With
``device_sync=False`` (default) the attached value is ignored and spans time
dispatch only, so instrumentation never costs a sync unless asked to. A span
that has ended holds no array.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time

import jax

RING_CAPACITY = 65_536  # ended spans kept, process-wide; the oldest fall out
# kept spans, process-wide; past it new ones are dropped and counted. An eager
# operation is three (trace, lower, compile) and a float32 reference dispatches
# hundreds: 16,384 is ~8 MB at worst
KEPT_CAPACITY = 16_384

_ring: collections.deque = collections.deque(maxlen=RING_CAPACITY)
_kept: list = []
_kept_dropped = 0
_ring_lock = threading.Lock()  # the ring, the kept list and its count
_next_id = itertools.count(1).__next__
_tls = threading.local()  # .stack: the thread's open spans; .xla: its listener state


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def _end(span: "Span") -> None:
    global _kept_dropped
    with _ring_lock:
        _ring.append(span)
        if span.keep:
            if len(_kept) < KEPT_CAPACITY:
                _kept.append(span)
            else:
                _kept_dropped += 1


def spans(since: float = float("-inf")) -> list:
    """The ended spans that began at or after ``since`` (``time.perf_counter()``
    seconds): those still in the ring and the kept ones that fell out of it,
    once each, in the order they ended."""
    with _ring_lock:  # held for the copies only: span exits wait on it
        ended = list(_ring)
        kept = list(_kept) if len(ended) == RING_CAPACITY else ()
    if kept:  # a kept span not in the ring ended before everything in it
        in_ring = {sp.id for sp in ended}
        ended = [sp for sp in kept if sp.id not in in_ring] + ended
    return [sp for sp in ended if sp.t0 >= since]


def kept_stats() -> dict:
    """The kept list's length, its cap and what was dropped past it."""
    with _ring_lock:
        return {"kept": len(_kept), "dropped": _kept_dropped, "capacity": KEPT_CAPACITY}


def clear_spans() -> None:
    global _kept_dropped
    with _ring_lock:
        _ring.clear()
        _kept.clear()
        _kept_dropped = 0


class Span:
    """One region; the ring's record once it has ended. Use via
    ``SpanTracer.span`` (context manager)."""

    __slots__ = ("id", "parent", "name", "path", "depth", "t0", "t1", "attrs",
                 "replica_id", "keep", "_keep_under", "_tracer", "_sync", "_ann")

    def __init__(self, tracer: "SpanTracer", name: str, sync, replica_id, attrs: dict,
                 keep: bool = False):
        self.id = 0
        self.parent = None
        self.name = name
        self.path = name
        self.depth = 0
        self.t0 = self.t1 = 0.0
        self.attrs = attrs
        self.replica_id = replica_id
        # outlives the ring (module docstring). ``keep`` is settable while the
        # span is open, for the span alone; given here it holds for what opens under it
        self.keep = self._keep_under = keep
        self._tracer = tracer
        self._sync = sync
        self._ann = None

    @property
    def dur_s(self) -> float:
        return self.t1 - self.t0

    def set_sync(self, value) -> None:
        """Arrange for the span to block on ``value`` (any array/pytree) at
        exit, making its duration device-accurate."""
        self._sync = value

    def annotate(self, **attrs) -> None:
        """Attach extra key/values to the span's record and JSONL event."""
        self.attrs.update(attrs)

    def as_dict(self) -> dict:
        return {"id": self.id, "parent": self.parent, "path": self.path, "t0": self.t0,
                "t1": self.t1, "replica_id": self.replica_id, **self.attrs}

    def __enter__(self) -> "Span":
        stack = _stack()
        if stack:
            up = stack[-1]
            self.parent, self.path, self.depth = up.id, f"{up.path}/{self.name}", len(stack)
            if self.replica_id is None:
                self.replica_id = up.replica_id
            if up._keep_under:
                self.keep = self._keep_under = True
        self.id = _next_id()
        self._ann = jax.profiler.TraceAnnotation(self.path)
        self._ann.__enter__()
        stack.append(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        tracer, sync = self._tracer, self._sync
        try:
            # a failing async computation surfaces HERE in device_sync mode —
            # the annotation/stack cleanup below must still run or every
            # later span on this thread inherits a corrupted nesting path
            if exc_type is None and sync is not None and tracer.device_sync:
                jax.block_until_ready(sync)
        finally:
            self.t1 = time.perf_counter()
            self._ann.__exit__(exc_type, exc, tb)
            self._tracer = self._sync = self._ann = None
            stack = _stack()
            if stack and stack[-1] is self:
                stack.pop()
            if exc_type is not None:
                self.attrs["error"] = exc_type.__name__
            _end(self)
        if exc_type is None:
            tracer._emit(self)


class SpanTracer:
    def __init__(self, sink=None, device_sync: bool = False):
        self.sink = sink
        self.device_sync = device_sync

    def span(self, name: str, sync=None, replica_id=None, keep: bool = False,
             **attrs) -> Span:
        """Open a nested span: ``with tracer.span("decode") as sp: ...``.

        ``sync``: optional value to block on at exit. Blocking only happens
        when the tracer was built with ``device_sync=True`` — instrumented
        code can attach sync values unconditionally and the config knob
        decides whether spans pay the device round-trip. ``replica_id``: the
        owning engine's; spans opened inside inherit it. ``keep``: the span
        and every span opened inside it outlive the ring.
        """
        return Span(self, name, sync, replica_id, attrs, keep)

    def _emit(self, span: Span) -> None:
        if self.sink is not None:
            ev = {
                "type": "span",
                "name": span.name,
                "path": span.path,
                "depth": span.depth,
                "id": span.id,
                "parent": span.parent,
                "start_s": round(span.t0, 6),
                "dur_s": span.t1 - span.t0,
            }
            if span.replica_id is not None:
                ev["replica_id"] = span.replica_id
            if span.attrs:
                ev.update(span.attrs)
            self.sink.emit(ev)


DEFAULT = SpanTracer()  # for spans opened before any Telemetry exists (an engine's build)


def span(name: str, replica_id=None, keep: bool = False, **attrs) -> Span:
    """``SpanTracer.span`` on the module's default tracer: no sink and no device
    sync, so it takes no ``sync`` value. Code that holds a ``Telemetry`` opens
    its spans there."""
    return Span(DEFAULT, name, None, replica_id, attrs, keep)


# -- every trace, lowering and compile of the process -------------------------

_XLA_KINDS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
              "/jax/core/compile/backend_compile_duration": "compile"}
_CACHE_VERDICTS = {"/jax/compilation_cache/cache_hits": "hit",
                   "/jax/compilation_cache/cache_misses": "written"}  # jax records it where it WRITES
_CACHE_SECONDS = {"/jax/compilation_cache/cache_retrieval_time_sec": "load_s",
                  "/jax/compilation_cache/compile_time_saved_sec": "saved_s"}
XLA_TOTALS = ("trace_s", "lower_s", "backend_s", "hit", "written", "not_kept")
_NO_TOTALS = (0.0, 0.0, 0.0, 0, 0, 0)


class _XlaState:
    """One thread's listener state: how deep it is in jax's trace / lower /
    compile brackets, the outermost one (open), and its running totals."""

    __slots__ = ("depth", "kind", "t0", "program", "inner", "attrs", "totals")

    def __init__(self):
        self.depth = 0
        self.totals = _NO_TOTALS


def _xla_state() -> _XlaState:
    st = getattr(_tls, "xla", None)
    if st is None:
        st = _tls.xla = _XlaState()
    return st


def xla_totals() -> tuple:
    """This thread's running sums over its ended ``xla/*`` spans, in
    ``XLA_TOTALS``' order: seconds traced, lowered and in the backend (compiled
    or loaded), and the compiles by the cache's verdict. Two readings bracket
    whatever ran between them on the thread."""
    return _xla_state().totals


def _on_begin(event: str, value, fun_name=None, **_) -> None:
    kind = _XLA_KINDS.get(event)
    if kind is None:
        return
    st = _xla_state()
    if st.depth == 0:
        st.kind, st.t0, st.program, st.inner, st.attrs = kind, time.perf_counter(), fun_name, 0, {}
    else:
        st.inner += 1
    st.depth += 1


def _on_duration(event: str, duration, **_) -> None:
    kind = _XLA_KINDS.get(event)
    if kind is None:
        field = _CACHE_SECONDS.get(event)
        if field is not None and _xla_state().depth:
            _tls.xla.attrs[field] = float(duration)
        return
    st = _xla_state()
    if st.depth == 0:  # began before the listener was there
        return
    st.depth -= 1
    if st.depth:
        return
    t1 = time.perf_counter()
    sp = Span(None, f"xla/{st.kind}", None, None,
              {"program": st.program, "inner": st.inner, **st.attrs}, keep=True)
    stack = _stack()
    if stack:
        up = stack[-1]
        sp.parent, sp.path, sp.depth = up.id, f"{up.path}/{sp.name}", len(stack)
        sp.replica_id = up.replica_id
    sp.id, sp.t0, sp.t1 = _next_id(), st.t0, t1
    trace_s, lower_s, backend_s, hit, written, not_kept = st.totals
    dur = t1 - st.t0
    if st.kind == "trace":
        trace_s += dur
    elif st.kind == "lower":
        lower_s += dur
    else:
        verdict = sp.attrs.setdefault("cache", "not_kept")
        backend_s += dur
        hit += verdict == "hit"
        written += verdict == "written"
        not_kept += verdict == "not_kept"
    st.totals = (trace_s, lower_s, backend_s, hit, written, not_kept)
    _end(sp)


def _on_event(event: str, **_) -> None:
    verdict = _CACHE_VERDICTS.get(event)
    if verdict is not None and _xla_state().depth:
        _tls.xla.attrs["cache"] = verdict


def listen(on: bool = True) -> None:
    """Install the listener (once a process: a module reloaded keeps the first),
    or with ``on=False`` take it off jax's lists again: no ``xla/*`` span is made
    and ``xla_totals()`` stand still until it is installed anew. Call it between
    programs, not inside one: a thread that is then inside a trace or a compile
    keeps its depth."""
    import jax.monitoring as monitoring

    installed = getattr(monitoring, "_deepspeed_tpu_xla_spans", None)
    if on and installed is None:
        monitoring._deepspeed_tpu_xla_spans = (_on_begin, _on_duration, _on_event)
        monitoring.register_scalar_listener(_on_begin)
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
    elif not on and installed is not None:
        monitoring._deepspeed_tpu_xla_spans = None
        monitoring.unregister_scalar_listener(installed[0])
        monitoring.unregister_event_duration_listener(installed[1])
        monitoring.unregister_event_listener(installed[2])


listen()


# -- what starting up was made of ----------------------------------------------

STARTUP = "startup/build"  # opened by build_serving_engine and deepspeed_tpu.initialize


def sum_kept(kept) -> tuple:
    """``(phases, programs)`` of a list of kept spans: seconds by path of
    ``startup/build`` and the spans under it (the ``xla/*`` ones left to the
    second table), and the ``xla/*`` spans by jax's ``program``: ``under`` (the
    paths they happened under; None: no span was open), ``trace_s``,
    ``lower_s``, ``compile_s`` (really compiled), ``load_s`` (a cache hit's
    whole backend time) and the count of each cache verdict; most seconds
    first."""
    phases: dict = {}
    programs: dict = {}
    for sp in kept:
        if sp.name.startswith("xla/"):
            name = str(sp.attrs.get("program"))  # a trace says "f" where its compile says "jit(f)"
            name = name[4:-1] if name.startswith("jit(") and name.endswith(")") else name
            row = programs.setdefault(name, {
                "program": name, "under": [], "trace_s": 0.0, "lower_s": 0.0,
                "compile_s": 0.0, "load_s": 0.0, "hit": 0, "written": 0, "not_kept": 0})
            under = sp.path[:-len(sp.name)].rstrip("/") or None
            if under not in row["under"]:
                row["under"].append(under)
            kind, verdict = sp.name[4:], sp.attrs.get("cache")
            if kind != "compile":
                row[f"{kind}_s"] += sp.t1 - sp.t0
            else:
                row["load_s" if verdict == "hit" else "compile_s"] += sp.t1 - sp.t0
                row[verdict] += 1
        elif sp.path.startswith(STARTUP):
            phases[sp.path] = phases.get(sp.path, 0.0) + sp.t1 - sp.t0
    rows = sorted(programs.values(), key=lambda r: -(
        r["trace_s"] + r["lower_s"] + r["compile_s"] + r["load_s"]))
    return phases, rows


def startup_table(since: float = float("-inf")) -> dict:
    """The kept spans that ended at or after ``since``, summed (``sum_kept``):
    ``phases``, ``programs``, and ``kept`` (``kept_stats()``)."""
    with _ring_lock:
        kept = [sp for sp in _kept if sp.t1 >= since]
    phases, programs = sum_kept(kept)
    return {"phases": phases, "programs": programs, "kept": kept_stats()}
