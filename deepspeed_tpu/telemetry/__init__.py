"""Unified telemetry: metrics registry + span tracing + recompile watchdog +
exporters.

One spine for "what is slow, what recompiled, and what is each request
experiencing" (SURVEY §5 observability; the reference's MonitorMaster /
CommsLogger / nvtx / flops-profiler islands, unified):

  * ``MetricsRegistry`` — counters, gauges, log-bucketed histograms with
    p50/p90/p99 estimates, cheap enough for per-decode-step updates.
  * ``SpanTracer`` — nested host spans that also open
    ``jax.profiler.TraceAnnotation`` ranges (JSONL + XPlane, one API).
  * ``RecompileWatchdog`` — wraps jitted entry points; every compilation is
    an event; paths declared compile-stable (serving decode) warn/raise on a
    second compilation.
  * ``ProgramLedger`` — XLA cost model (flops/bytes/HBM) per watched
    program, joined with the wall-time histograms into MFU + roofline rows
    (telemetry/program_ledger.py; docs/observability.md).
  * ``RequestTracer`` — bounded per-request lifecycle timeline with a
    Perfetto export (telemetry/request_trace.py).
  * exporters — JSONL event log, Prometheus text, MonitorMaster bridge.

``Telemetry`` bundles them with one config surface; engines hold one
instance each. Metric names follow ``subsystem/name``
(docs/observability.md is the catalog).
"""

from .collective_ledger import (CollectiveLedger, parse_hlo_collectives,
                                pipeline_bubble_fraction, step_anatomy,
                                summarize_collectives)
from .exporters import (JsonlExporter, MonitorBridge, prometheus_fleet_text,
                        prometheus_text)
from .incident import IncidentRecorder
from .program_ledger import (ProgramLedger, aot_cost, hbm_snapshot,
                             platform_peaks, tree_bytes)
from .registry import Counter, Gauge, Histogram, MetricsRegistry, get_registry
from .request_trace import RequestTracer, request_timeline, to_perfetto
from .slo import SLOTracker, classify_terminal
from .timeseries import TimeSeriesStore
from .tracing import Span, SpanTracer, startup_table
from .watchdog import RecompileError, RecompileWatchdog, abstract_signature

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "get_registry",
    "Span", "SpanTracer", "RecompileError", "RecompileWatchdog",
    "abstract_signature", "JsonlExporter", "MonitorBridge", "prometheus_text",
    "prometheus_fleet_text", "ProgramLedger", "aot_cost", "hbm_snapshot",
    "platform_peaks", "tree_bytes", "RequestTracer", "request_timeline",
    "to_perfetto", "CollectiveLedger", "parse_hlo_collectives",
    "summarize_collectives", "step_anatomy", "pipeline_bubble_fraction",
    "Telemetry", "TimeSeriesStore", "SLOTracker", "classify_terminal",
    "IncidentRecorder",
]


class Telemetry:
    """One registry + tracer + watchdog + program ledger + optional JSONL
    sink.

    ``registry=None`` creates a private registry (engine-scoped metrics
    should not mix across engine instances); pass ``get_registry()`` to
    share the process-global one instead. ``ledger=False`` disables the
    cost-model capture (``telemetry.ledger.enabled`` in config).
    """

    def __init__(self, registry: MetricsRegistry | None = None,
                 jsonl_path: str = "", watchdog_mode: str = "warn",
                 device_sync_spans: bool = False, ledger: bool = True,
                 ledger_collectives: bool = True, ici_gbps: float = 0.0,
                 jsonl_max_bytes: int = 0, jsonl_keep: int = 3):
        self.registry = registry if registry is not None else MetricsRegistry()
        self.sink = JsonlExporter(jsonl_path, max_bytes=jsonl_max_bytes,
                                  keep=jsonl_keep) if jsonl_path else None
        self.tracer = SpanTracer(self.sink, device_sync=device_sync_spans)
        self.ledger = ProgramLedger(self.registry, enabled=ledger,
                                    collectives=ledger_collectives,
                                    ici_gbps=ici_gbps)
        self.watchdog = RecompileWatchdog(self.registry, self.sink,
                                          mode=watchdog_mode,
                                          ledger=self.ledger)

    # convenience passthroughs — instrumented code holds one handle
    def counter(self, name: str) -> Counter:
        return self.registry.counter(name)

    def gauge(self, name: str) -> Gauge:
        return self.registry.gauge(name)

    def histogram(self, name: str) -> Histogram:
        return self.registry.histogram(name)

    def span(self, name: str, sync=None, **attrs):
        return self.tracer.span(name, sync=sync, **attrs)

    def watch(self, fn, name: str, stable: bool = False):
        return self.watchdog.watch(fn, name, stable=stable)

    def emit(self, event: dict) -> None:
        if self.sink is not None:
            self.sink.emit(event)

    def snapshot(self, **extra) -> dict:
        """Registry snapshot + recompile table + ``startup`` (what coming up
        was made of: ``tracing.startup_table``, the process's kept spans) +
        program ledger + step anatomy (+ caller extras), the one call that
        reports everything. The
        ledger table and anatomy are computed FIRST so the MFU/intensity and
        ``<prefix>/comm/*`` gauges they publish land in the same metrics
        snapshot."""
        out: dict = {}
        if self.ledger.enabled and self.ledger.entries:
            out["program_ledger"] = self.ledger.table(self.registry)
            out["step_anatomy"] = self.ledger.anatomy(self.registry)
            out["platform"] = dict(self.ledger.platform)
            rec = self._comm_reconcile()
            if rec:
                out["comm_reconcile"] = rec
        out["metrics"] = self.registry.snapshot()
        out["recompile_table"] = self.watchdog.compile_table()
        out["startup"] = startup_table()
        out.update(extra)
        return out

    def _comm_reconcile(self):
        """Cross-check the host-side comm byte accounting (comm/logger.py)
        against the HLO-derived per-axis totals — an axis XLA compiled
        collectives over that the host accounting never saw is a collective
        that bypassed the ``comm/`` wrappers (the report renders these as
        labeled warnings, never averages them away)."""
        coll = self.ledger.collectives
        if not coll.programs:
            return None
        from ..comm.logger import comms_logger

        if not comms_logger.enabled and not comms_logger.axis_totals():
            return None  # no host accounting to reconcile against
        return comms_logger.reconcile(coll.bytes_by_axis(),
                                      mesh_shape=coll.mesh_shape)

    def close(self) -> None:
        if self.sink is not None:
            self.sink.close()
