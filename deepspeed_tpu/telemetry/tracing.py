"""The program's one span system: nested host spans, kept in a ring, on the
clock everything else uses.

A span is a named wall-clock region of host code. Each span:

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

_ring: collections.deque = collections.deque(maxlen=RING_CAPACITY)
_ring_lock = threading.Lock()
_next_id = itertools.count(1).__next__


def spans(since: float = float("-inf")) -> list:
    """The ended spans still in the ring that began at or after ``since``
    (``time.perf_counter()`` seconds), in the order they ended."""
    with _ring_lock:  # held for the copy only: span exits wait on it
        ended = list(_ring)
    return [sp for sp in ended if sp.t0 >= since]


def clear_spans() -> None:
    with _ring_lock:
        _ring.clear()


class Span:
    """One region; the ring's record once it has ended. Use via
    ``SpanTracer.span`` (context manager)."""

    __slots__ = ("id", "parent", "name", "path", "depth", "t0", "t1", "attrs",
                 "replica_id", "_tracer", "_sync", "_ann")

    def __init__(self, tracer: "SpanTracer", name: str, sync, replica_id, attrs: dict):
        self.id = 0
        self.parent = None
        self.name = name
        self.path = name
        self.depth = 0
        self.t0 = self.t1 = 0.0
        self.attrs = attrs
        self.replica_id = replica_id
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
        stack = self._tracer._stack()
        if stack:
            up = stack[-1]
            self.parent, self.path, self.depth = up.id, f"{up.path}/{self.name}", len(stack)
            if self.replica_id is None:
                self.replica_id = up.replica_id
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
            stack = tracer._stack()
            if stack and stack[-1] is self:
                stack.pop()
            if exc_type is not None:
                self.attrs["error"] = exc_type.__name__
            with _ring_lock:
                _ring.append(self)
        if exc_type is None:
            tracer._emit(self)


class SpanTracer:
    def __init__(self, sink=None, device_sync: bool = False):
        self.sink = sink
        self.device_sync = device_sync
        self._tls = threading.local()

    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def span(self, name: str, sync=None, replica_id=None, **attrs) -> Span:
        """Open a nested span: ``with tracer.span("decode") as sp: ...``.

        ``sync``: optional value to block on at exit. Blocking only happens
        when the tracer was built with ``device_sync=True`` — instrumented
        code can attach sync values unconditionally and the config knob
        decides whether spans pay the device round-trip. ``replica_id``: the
        owning engine's; spans opened inside inherit it.
        """
        return Span(self, name, sync, replica_id, attrs)

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
