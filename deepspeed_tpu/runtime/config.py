"""JSON config → typed config tree.

TPU-native re-design of ``DeepSpeedConfig`` (reference: runtime/config.py:755).
The reference mixes two schema generations (hand-rolled ``get_scalar_param``
readers and pydantic models, runtime/config_utils.py); here there is a single
generation of dataclasses from day one (SURVEY.md §5 "Config / flag system").
User-facing JSON keys keep DeepSpeed spelling so existing configs load
unchanged — including batch-size triangulation
(train = micro × gas × dp_world, reference runtime/config.py:846-905).
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Any, Optional

from . import constants as C


class DeepSpeedConfigError(Exception):
    pass


def _sub(d: dict, key: str) -> dict:
    v = d.get(key, {})
    if v is None:
        return {}
    if not isinstance(v, dict):
        raise DeepSpeedConfigError(f"'{key}' must be an object, got {type(v)}")
    return v


def _filter_kwargs(cls, d: dict) -> dict:
    names = {f.name for f in dataclasses.fields(cls)}
    return {k: v for k, v in d.items() if k in names}


def _build(cls, d: dict):
    return cls(**_filter_kwargs(cls, d))


@dataclass
class DebugConfig:
    """Numerics / memory sanitizers (SURVEY §5 race-detection row; reference
    analogues: torch anomaly detection + DS's overflow tracing).

    - ``nan_check``: enables ``jax_debug_nans`` — every primitive result is
      re-checked and the FIRST NaN/Inf-producing op raises with its source
      location, instead of a NaN surfacing steps later in the loss. State
      donation is disabled in this mode (re-execution for localisation needs
      the inputs alive). Debug-only: each op syncs.
    - ``donation_check``: after the first compiled step, verify the donated
      state buffers were actually consumed (aliased into the new state) —
      a silent donation fallback (e.g. a sharding/layout mismatch) doubles
      resident state memory without any error.
    """

    nan_check: bool = False
    donation_check: bool = False


@dataclass
class FP16Config:
    """reference: runtime/config.py fp16 block + fp16/loss_scaler.py."""

    enabled: bool = False
    loss_scale: float = 0.0  # 0 = dynamic
    initial_scale_power: int = 16
    loss_scale_window: int = 1000
    hysteresis: int = 2
    min_loss_scale: float = 1.0


@dataclass
class BF16Config:
    enabled: bool = False


@dataclass
class OffloadConfig:
    """zero offload sub-configs (reference: runtime/zero/offload_config.py)."""

    device: str = "none"  # none | cpu | nvme
    nvme_path: str = "/tmp/dstpu_nvme"
    pin_memory: bool = True
    buffer_count: int = 4
    fast_init: bool = False


@dataclass
class ZeroConfig:
    """reference: runtime/zero/config.py:77 DeepSpeedZeroConfig.

    On TPU the stage number selects a *sharding rule set*, not a hand-managed
    partitioning runtime (SURVEY.md §7):
      0: replicated params/grads/opt state, psum grads
      1: optimizer state sharded over (data, fsdp)
      2: + gradients reduce-scattered
      3: + parameters sharded (FSDP); XLA all-gathers at use
    """

    stage: int = 0
    contiguous_gradients: bool = True
    reduce_scatter: bool = True
    reduce_bucket_size: int = 5e8
    allgather_partitions: bool = True
    allgather_bucket_size: int = 5e8
    overlap_comm: bool = True
    round_robin_gradients: bool = False
    offload_param: OffloadConfig = field(default_factory=OffloadConfig)
    offload_optimizer: OffloadConfig = field(default_factory=OffloadConfig)
    sub_group_size: int = 1e9
    prefetch_bucket_size: int = 5e7
    param_persistence_threshold: int = 1e5
    max_live_parameters: int = 1e9
    max_reuse_distance: int = 1e9
    gather_16bit_weights_on_model_save: bool = False
    ignore_unused_parameters: bool = True
    zero_quantized_weights: bool = False

    def __post_init__(self):
        if isinstance(self.offload_param, dict):
            self.offload_param = _build(OffloadConfig, self.offload_param)
        if isinstance(self.offload_optimizer, dict):
            self.offload_optimizer = _build(OffloadConfig, self.offload_optimizer)
        if self.stage not in (0, 1, 2, 3):
            raise DeepSpeedConfigError(f"zero stage must be 0-3, got {self.stage}")


@dataclass
class OptimizerConfig:
    type: str = "adamw"
    params: dict = field(default_factory=dict)


@dataclass
class SchedulerConfig:
    type: Optional[str] = None
    params: dict = field(default_factory=dict)


@dataclass
class ActivationCheckpointingConfig:
    """reference: runtime/activation_checkpointing/checkpointing.py:825 configure().

    On TPU this maps to jax.checkpoint policies over the scanned layer stack;
    partition_activations maps to sharding the residual stream over 'model'.
    """

    partition_activations: bool = False
    cpu_checkpointing: bool = False
    contiguous_memory_optimization: bool = False
    number_checkpoints: Optional[int] = None
    synchronize_checkpoint_boundary: bool = False
    profile: bool = False
    # TPU-only: jax.checkpoint policy name (runtime/activation_checkpointing).
    # Empty = keep the model's own remat_policy (default save_flash, the
    # tuned fast path); the generic checkpoint() API treats empty as
    # nothing_saveable (full recompute).
    policy: str = ""
    enabled: bool = False


@dataclass
class FlopsProfilerConfig:
    enabled: bool = False
    profile_step: int = 1
    module_depth: int = -1
    top_modules: int = 1
    detailed: bool = True
    output_file: Optional[str] = None


@dataclass
class CommsLoggerConfig:
    enabled: bool = False
    verbose: bool = False
    prof_all: bool = True
    debug: bool = False


@dataclass
class MonitorBackendConfig:
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedJobName"
    team: str = ""
    group: str = ""
    project: str = "deepspeed"


@dataclass
class CollectiveLedgerConfig:
    """Collective X-ray sub-block (``telemetry.ledger.collectives``;
    ``telemetry/collective_ledger.py``, docs/observability.md "Collective
    X-ray"):

    - ``enabled``: parse each resolved program's post-optimization HLO for
      collective ops (payload bytes, mesh-axis attribution, static
      ``-start``/``-done`` overlap verdict) and derive the step-anatomy
      rows in ``telemetry_snapshot()``. Rides the program ledger's
      lazily-resolved executables — zero new XLA programs.
    - ``ici_gbps``: per-chip one-way ICI bandwidth override in GB/s for the
      comm-time model (0 = use the per-generation peak table; CPU/unknown
      platforms stay unrated unless overridden).
    """

    enabled: bool = True
    ici_gbps: float = 0.0

    def __post_init__(self):
        if self.ici_gbps < 0:
            raise DeepSpeedConfigError(
                f"telemetry.ledger.collectives.ici_gbps must be >= 0, "
                f"got {self.ici_gbps}")


@dataclass
class LedgerConfig:
    """Program-ledger sub-block (``telemetry.ledger``;
    ``telemetry/program_ledger.py``, docs/observability.md):

    - ``enabled``: capture the XLA cost model (flops, bytes accessed, HBM
      footprint) of every watchdog-wrapped program and derive MFU/roofline
      rows in ``telemetry_snapshot()``. Capture is host-side spec
      extraction; the XLA analysis is lazy (first snapshot) and served from
      the compilation cache — no new program shapes, no hot-path cost.
    - ``hbm_warn_fraction``: the HBM ledger flags the snapshot when device
      bytes-in-use exceeds this fraction of the backend's memory limit.
    - ``collectives``: collective X-ray sub-block (its own dataclass above).
    """

    enabled: bool = True
    hbm_warn_fraction: float = 0.9
    collectives: CollectiveLedgerConfig = field(
        default_factory=CollectiveLedgerConfig)

    def __post_init__(self):
        if isinstance(self.collectives, dict):
            self.collectives = _build(CollectiveLedgerConfig, self.collectives)
        if not (0.0 < self.hbm_warn_fraction <= 1.0):
            raise DeepSpeedConfigError(
                f"telemetry.ledger.hbm_warn_fraction must be in (0, 1], "
                f"got {self.hbm_warn_fraction}")


@dataclass
class RequestTraceConfig:
    """Per-request lifecycle tracing sub-block (``telemetry.request_trace``;
    ``telemetry/request_trace.py``, docs/observability.md):

    - ``enabled``: record arrived/admitted/chunk/first_token/terminal (and
      quarantine/failover) timeline events per request — host-side dict
      appends into a bounded ring buffer.
    - ``capacity``: ring-buffer size in EVENTS (oldest evicted first).
      A request produces ~5 events plus one per prefill chunk.
    """

    enabled: bool = True
    capacity: int = 2048

    def __post_init__(self):
        if self.capacity < 1:
            raise DeepSpeedConfigError(
                f"telemetry.request_trace.capacity must be >= 1, "
                f"got {self.capacity}")


@dataclass
class TimeSeriesConfig:
    """Flight-recorder ring sub-block (``telemetry.timeseries``, mirrored as
    ``serving.timeseries``; ``telemetry/timeseries.py``,
    docs/observability.md "Flight recorder & SLOs").

    - ``enabled``: sample the configured metric set into bounded
      downsampling rings from the owning step/serve loop. Forced on when
      ``slo`` or ``incidents`` is enabled (both read the rings).
    - ``interval_s``: raw sampling/bucket interval on the fleet clock.
    - ``tiers``: coarser bucket intervals (seconds) rebuilt alongside raw;
      intervals <= ``interval_s`` are dropped.
    - ``capacity``: cells kept PER TIER per series (fixed deques — memory
      is O(series x tiers x capacity) regardless of run length).
    - ``flush_capacity``: closed-raw-cell journal bound for the step-reply
      piggyback flush (seq-cursor; cells evicted before a flush are lost).
    """

    enabled: bool = False
    interval_s: float = 0.25
    tiers: list = field(default_factory=lambda: [1.0, 10.0, 60.0])
    capacity: int = 240
    flush_capacity: int = 4096

    def __post_init__(self):
        if self.interval_s <= 0:
            raise DeepSpeedConfigError(
                f"telemetry.timeseries.interval_s must be > 0, "
                f"got {self.interval_s}")
        if self.capacity < 2:
            raise DeepSpeedConfigError(
                f"telemetry.timeseries.capacity must be >= 2, "
                f"got {self.capacity}")
        if self.flush_capacity < 1:
            raise DeepSpeedConfigError(
                f"telemetry.timeseries.flush_capacity must be >= 1, "
                f"got {self.flush_capacity}")


@dataclass
class SLOConfig:
    """SLO objective sub-block (``telemetry.slo``, mirrored as
    ``serving.slo``; ``telemetry/slo.py``, docs/observability.md).

    - ``enabled``: classify terminals + evaluate attainment/burn on the
      rings, publishing the ``slo/*`` gauges.
    - ``ttft_s`` / ``tpot_s``: per-request latency objectives (seconds);
      a finished request exceeding one counts as that dimension's
      violation. 0 disables the dimension's classification.
    - ``ttft_target`` / ``tpot_target`` / ``availability_target``: the SLO
      targets in (0, 1] — the error budget is ``1 - target``.
    - ``window_s``: rolling attainment window on the fleet clock.
    - ``fast_window_s`` / ``slow_window_s``: the multi-window burn-rate
      pair (5m/1h analogues, scaled so drills can use second-scale
      windows).
    - ``fast_burn_threshold``: fast-window burn at/over which the verdict
      is a breach (14.4 = the classic "30-day budget gone in ~2 days"
      page threshold) — an incident trigger on the rising edge.
    - ``eval_interval_s``: how often the Router re-evaluates.
    """

    enabled: bool = False
    ttft_s: float = 0.0
    tpot_s: float = 0.0
    ttft_target: float = 0.99
    tpot_target: float = 0.99
    availability_target: float = 0.999
    window_s: float = 300.0
    fast_window_s: float = 300.0
    slow_window_s: float = 3600.0
    fast_burn_threshold: float = 14.4
    eval_interval_s: float = 1.0

    def __post_init__(self):
        for name in ("ttft_target", "tpot_target", "availability_target"):
            v = getattr(self, name)
            if not 0.0 < v <= 1.0:
                raise DeepSpeedConfigError(
                    f"telemetry.slo.{name} must be in (0, 1], got {v}")
        if self.ttft_s < 0 or self.tpot_s < 0:
            raise DeepSpeedConfigError(
                "telemetry.slo.ttft_s/tpot_s must be >= 0")
        for name in ("window_s", "fast_window_s", "slow_window_s",
                     "eval_interval_s"):
            if getattr(self, name) <= 0:
                raise DeepSpeedConfigError(
                    f"telemetry.slo.{name} must be > 0, "
                    f"got {getattr(self, name)}")
        if self.fast_burn_threshold <= 0:
            raise DeepSpeedConfigError(
                f"telemetry.slo.fast_burn_threshold must be > 0, "
                f"got {self.fast_burn_threshold}")


@dataclass
class IncidentConfig:
    """Incident-recorder sub-block (``telemetry.incidents``, mirrored as
    ``serving.incidents``; ``telemetry/incident.py``, docs/observability.md).

    - ``enabled``: stage/finalize durable incident bundles on the typed
      trigger matrix. Requires ``dir``.
    - ``dir``: bundle directory (the Router writes here; each replica's
      engine writes under ``<dir>/replica<rid>/``).
    - ``max_bundles``: bundle count bound per directory; oldest are
      LRU-pruned past it (storage stays O(configured capacity)).
    - ``window_before_s`` / ``window_after_s``: ring/trace capture window
      around the trigger; finalization waits ``window_after_s`` of fleet
      time so the aftermath is in the bundle too.
    """

    enabled: bool = False
    dir: str = ""
    max_bundles: int = 32
    window_before_s: float = 30.0
    window_after_s: float = 2.0

    def __post_init__(self):
        if self.enabled and not self.dir:
            raise DeepSpeedConfigError(
                "telemetry.incidents.enabled requires telemetry.incidents.dir")
        if self.max_bundles < 1:
            raise DeepSpeedConfigError(
                f"telemetry.incidents.max_bundles must be >= 1, "
                f"got {self.max_bundles}")
        if self.window_before_s < 0 or self.window_after_s < 0:
            raise DeepSpeedConfigError(
                "telemetry.incidents window_before_s/window_after_s "
                "must be >= 0")


@dataclass
class TelemetryConfig:
    """Unified telemetry block (``deepspeed_tpu/telemetry/``; docs/observability.md).

    The engine always keeps a per-instance metrics registry (host-side dict
    updates, no device syncs); this block controls the exporters and the
    recompile watchdog's response:

    - ``enabled``: master switch for the exporters (JSONL sink + monitor
      bridge). Metrics/compile accounting run regardless — they power
      ``engine.telemetry_snapshot()``.
    - ``jsonl_path``: append telemetry events (spans, compiles, snapshots)
      here; pretty-print with ``python -m deepspeed_tpu.telemetry.report``.
    - ``watchdog``: ``off | warn | raise`` — response when a compile-stable
      path (serving decode) compiles a second time. The train step is
      watched but never stable (curriculum/elastic batch shapes legitimately
      retrace).
    - ``device_sync_spans``: spans block on their attached output
      (``jax.block_until_ready``) for device-accurate durations — defeats
      async dispatch, profiling runs only.
    - ``monitor_bridge``: forward registry snapshots into the MonitorMaster
      backends at each print boundary.
    - ``ledger``: program-ledger sub-block (cost model + MFU/roofline;
      its own dataclass above).
    - ``request_trace``: per-request lifecycle tracing sub-block (serving
      engines; its own dataclass above).
    - ``jsonl_max_bytes``: size-based JSONL rotation threshold — when an
      append would grow the file past it, the file is rename-rotated to
      ``<path>.1`` (older files shift up) before the append. 0 = never
      rotate (the pre-rotation behavior).
    - ``jsonl_keep``: rotated files retained (``.1`` newest); older are
      deleted.
    - ``timeseries`` / ``slo`` / ``incidents``: flight-recorder sub-blocks
      (their own dataclasses above; docs/observability.md "Flight
      recorder & SLOs").
    """

    enabled: bool = False
    jsonl_path: str = ""
    jsonl_max_bytes: int = 0
    jsonl_keep: int = 3
    watchdog: str = "warn"
    device_sync_spans: bool = False
    monitor_bridge: bool = True
    ledger: LedgerConfig = field(default_factory=LedgerConfig)
    request_trace: RequestTraceConfig = field(default_factory=RequestTraceConfig)
    timeseries: TimeSeriesConfig = field(default_factory=TimeSeriesConfig)
    slo: SLOConfig = field(default_factory=SLOConfig)
    incidents: IncidentConfig = field(default_factory=IncidentConfig)

    def __post_init__(self):
        if isinstance(self.ledger, dict):
            self.ledger = _build(LedgerConfig, self.ledger)
        if isinstance(self.request_trace, dict):
            self.request_trace = _build(RequestTraceConfig, self.request_trace)
        if isinstance(self.timeseries, dict):
            self.timeseries = _build(TimeSeriesConfig, self.timeseries)
        if isinstance(self.slo, dict):
            self.slo = _build(SLOConfig, self.slo)
        if isinstance(self.incidents, dict):
            self.incidents = _build(IncidentConfig, self.incidents)
        if self.watchdog not in ("off", "warn", "raise"):
            raise DeepSpeedConfigError(
                f"telemetry.watchdog must be off|warn|raise, got {self.watchdog!r}")
        if self.jsonl_max_bytes < 0:
            raise DeepSpeedConfigError(
                f"telemetry.jsonl_max_bytes must be >= 0, "
                f"got {self.jsonl_max_bytes}")
        if self.jsonl_keep < 1:
            raise DeepSpeedConfigError(
                f"telemetry.jsonl_keep must be >= 1, got {self.jsonl_keep}")


@dataclass
class FaultInjectionConfig:
    """Deterministic fault-injection block (``resilience.fault_injection``
    for training/checkpointing, ``serving.fault_injection`` for the serving
    engine; consumed by ``resilience/faults.FaultInjector``;
    docs/resilience.md).

    Two selection modes compose: the deterministic lists fire exactly once
    per listed key (a rewound step / requeued request is not re-faulted —
    transient-fault model), and ``rate`` adds an independent seeded draw per
    opportunity (for randomized smoke runs, e.g. ``drills.py --fault-rate``).

    - ``nan_grad_steps``: 1-based global steps whose gradients go non-finite.
    - ``io_error_writes``: 1-based indices of guarded checkpoint file writes
      that raise ``OSError`` (permanent — retries must NOT mask it).
    - ``io_flaky_writes``: 1-based indices of guarded writes that raise a
      *transient* ``TransientIOError`` — the write clock advances across
      retries, so a retried save succeeds (the ``resilience.retry`` proof
      site).
    - ``io_error_journal_appends``: 1-based indices of request-journal
      appends that fail permanently (the ENOSPC/full-disk model, its own
      clock separate from the checkpoint write clock) — the journal goes
      fail-closed and the accept path rejects with ``journal_unavailable``
      (``inference/journal.py`` consumes this; docs/resilience.md).
    - ``garbage_logits_uids`` (+ ``garbage_logits_phase`` ``prefill|decode``,
      ``garbage_logits_decode_step`` 0-based): serving requests whose slot KV
      is poisoned so the compiled program genuinely computes NaN logits.
    - ``preempt_steps``: 1-based global steps before which a
      ``PreemptionSignal`` is raised (pre-dispatch: state is checkpointable).
    - ``replica_dead_at`` / ``replica_hang_at``: ``[replica_id, router_step]``
      pairs (1-based steps) at which a serving Router replica is found dead
      before its step, or its step is observed past ``health.timeout``
      (inference/router.py consumes these; engines ignore them).
    - ``rpc_timeout_at`` / ``rpc_conn_reset_at`` / ``rpc_garbled_at``:
      ``[method, nth_call]`` pairs (1-based per-client per-method call
      clocks) at which the serving RPC transport loses a reply to its
      deadline, drops the connection after the call executes, or corrupts
      the reply frame (``inference/rpc.py`` consumes these client-side).
    - ``gateway_disconnect_at`` / ``gateway_stall_at``: ``[uid, nth_token]``
      pairs (1-based token counts) at which the HTTP gateway's SSE stream
      for request ``uid`` observes its client vanish (disconnect) or stop
      reading (slow-reader write stall) — both must free the request's
      slot via ``Router.cancel`` (``launcher/http_gateway.py`` consumes
      these server-side; docs/resilience.md).
    - ``router_crash_at``: 1-based router steps at which the control plane
      "dies" — ``Router.step`` raises a typed ``ControlPlaneCrash`` so
      in-process recovery tests can abandon the Router mid-traffic and
      rebuild one over the same replicas + journal (the deterministic
      spelling of the ``drills.py --router-chaos`` SIGKILL;
      ``inference/router.py`` consumes this).
    - ``rate`` in [0, 1] with optional ``sites`` allowlist
      (``nan_grads`` | ``io_error`` | ``io_flaky`` | ``garbage_logits`` |
      ``preempt`` | ``replica_dead`` | ``replica_hang``).
    """

    enabled: bool = False
    seed: int = 0
    rate: float = 0.0
    sites: list = field(default_factory=list)
    nan_grad_steps: list = field(default_factory=list)
    io_error_writes: list = field(default_factory=list)
    io_flaky_writes: list = field(default_factory=list)
    io_error_journal_appends: list = field(default_factory=list)
    garbage_logits_uids: list = field(default_factory=list)
    garbage_logits_phase: str = "decode"
    garbage_logits_decode_step: int = 0
    preempt_steps: list = field(default_factory=list)
    replica_dead_at: list = field(default_factory=list)
    replica_hang_at: list = field(default_factory=list)
    rpc_timeout_at: list = field(default_factory=list)
    rpc_conn_reset_at: list = field(default_factory=list)
    rpc_garbled_at: list = field(default_factory=list)
    gateway_disconnect_at: list = field(default_factory=list)
    gateway_stall_at: list = field(default_factory=list)
    router_crash_at: list = field(default_factory=list)

    def __post_init__(self):
        if not 0.0 <= self.rate <= 1.0:
            raise DeepSpeedConfigError(
                f"fault_injection.rate must be in [0, 1], got {self.rate}")
        if self.garbage_logits_phase not in ("prefill", "decode"):
            raise DeepSpeedConfigError(
                "fault_injection.garbage_logits_phase must be prefill|decode, "
                f"got {self.garbage_logits_phase!r}")
        bad = set(self.sites) - {"nan_grads", "io_error", "io_flaky",
                                 "garbage_logits", "preempt",
                                 "replica_dead", "replica_hang",
                                 "rpc_timeout", "rpc_conn_reset",
                                 "rpc_garbled_frame",
                                 "gateway_disconnect", "gateway_stall",
                                 "router_crash"}
        if bad:
            raise DeepSpeedConfigError(
                f"fault_injection.sites contains unknown site(s) {sorted(bad)}")
        for name in ("replica_dead_at", "replica_hang_at"):
            for p in getattr(self, name):
                if (not isinstance(p, (list, tuple)) or len(p) != 2
                        or not all(isinstance(x, int) for x in p)):
                    raise DeepSpeedConfigError(
                        f"fault_injection.{name} entries must be "
                        f"[replica_id, router_step] int pairs, got {p!r}")
        for name in ("rpc_timeout_at", "rpc_conn_reset_at", "rpc_garbled_at"):
            for p in getattr(self, name):
                if (not isinstance(p, (list, tuple)) or len(p) != 2
                        or not isinstance(p[0], str)
                        or not isinstance(p[1], int)):
                    raise DeepSpeedConfigError(
                        f"fault_injection.{name} entries must be "
                        f"[method, nth_call] (str, int) pairs, got {p!r}")
        for name in ("gateway_disconnect_at", "gateway_stall_at"):
            for p in getattr(self, name):
                if (not isinstance(p, (list, tuple)) or len(p) != 2
                        or not all(isinstance(x, int) for x in p)):
                    raise DeepSpeedConfigError(
                        f"fault_injection.{name} entries must be "
                        f"[uid, nth_token] int pairs, got {p!r}")
        for s in self.router_crash_at:
            if not isinstance(s, int) or s < 1:
                raise DeepSpeedConfigError(
                    f"fault_injection.router_crash_at entries must be "
                    f"1-based router steps (positive ints), got {s!r}")
        for s in self.io_error_journal_appends:
            if not isinstance(s, int) or s < 1:
                raise DeepSpeedConfigError(
                    f"fault_injection.io_error_journal_appends entries must "
                    f"be 1-based append indices (positive ints), got {s!r}")


@dataclass
class PreemptionConfig:
    """``resilience.preemption`` block (consumed by ``runtime/engine.py`` +
    ``resilience/preemption.PreemptionGuard``; docs/resilience.md).

    - ``enabled``: install SIGTERM/SIGINT handlers at engine init; the flag
      is consumed at the next step boundary, where the engine takes a
      just-in-time atomic checkpoint and raises ``PreemptionSignal`` —
      the same code path the fault injector's ``preempt`` site drives.
    - ``save_dir``: where the JIT checkpoint lands (with a durable 'latest'
      repoint). Empty = no JIT checkpoint; the signal still surfaces as
      ``PreemptionSignal`` and the caller owns saving (the pre-PR 5
      behavior).
    - ``tag``: the JIT checkpoint's tag (re-saved over on every preemption;
      the atomic re-save-over-tag protocol keeps every crash window safe).
    - ``signals``: handler set, by name.
    """

    enabled: bool = False
    save_dir: str = ""
    tag: str = "preempt"
    signals: list = field(default_factory=lambda: ["SIGTERM", "SIGINT"])

    def __post_init__(self):
        import signal as _signal

        if not self.tag or "/" in self.tag:
            raise DeepSpeedConfigError(
                f"resilience.preemption.tag must be a plain tag name, got "
                f"{self.tag!r}")
        for name in self.signals:
            if not isinstance(name, str) or not name.startswith("SIG"):
                raise DeepSpeedConfigError(
                    f"resilience.preemption.signals entries must be signal "
                    f"names like 'SIGTERM', got {name!r}")
            if not hasattr(_signal, name):
                raise DeepSpeedConfigError(
                    f"resilience.preemption.signals: unknown signal {name!r}")
            if name in ("SIGKILL", "SIGSTOP"):
                # uncatchable by POSIX — signal.signal() would raise OSError
                # at engine init, long after this config was accepted
                raise DeepSpeedConfigError(
                    f"resilience.preemption.signals: {name} cannot be "
                    "caught; a handler can never run for it")


@dataclass
class RetryConfig:
    """``resilience.retry`` block (consumed by ``resilience/retry.py``
    wrappers around checkpoint I/O; the elastic agent reuses the same
    backoff math for relaunch spacing; docs/resilience.md).

    ``max_attempts`` bounds total tries (1 = no retries); delays grow
    ``base_delay_s * 2**(attempt-1)`` capped at ``max_delay_s``, spread by
    +/- ``jitter`` with a deterministic seeded draw."""

    max_attempts: int = 3
    base_delay_s: float = 0.5
    max_delay_s: float = 8.0
    jitter: float = 0.25

    def __post_init__(self):
        if self.max_attempts < 1:
            raise DeepSpeedConfigError(
                f"resilience.retry.max_attempts must be >= 1, got "
                f"{self.max_attempts}")
        if self.base_delay_s < 0 or self.max_delay_s < 0:
            raise DeepSpeedConfigError("resilience.retry delays must be >= 0")
        if not 0.0 <= self.jitter <= 1.0:
            raise DeepSpeedConfigError(
                f"resilience.retry.jitter must be in [0, 1], got {self.jitter}")


@dataclass
class ChaosConfig:
    """``resilience.chaos`` block (consumed by ``resilience/chaos.py`` and
    the ``drills.py --chaos-search`` drill; docs/resilience.md "Chaos
    conductor").

    - ``n_schedules``: schedules per search run (each a pure function of
      ``seed`` + schedule index).
    - ``seed``: search seed — same seed, same schedules, same artifacts.
    - ``max_faults``: entries per generated schedule (1..max_faults drawn).
    - ``artifact_dir``: where minimal ``chaos-repro-NNN.json`` reproducers
      land (rename-durable writes).
    - ``shrink``: delta-debug violating schedules to a minimal reproducer
      before writing the artifact (off = write the full schedule).
    """

    n_schedules: int = 64
    seed: int = 0
    max_faults: int = 4
    artifact_dir: str = "chaos-repros"
    shrink: bool = True

    def __post_init__(self):
        if self.n_schedules < 1:
            raise DeepSpeedConfigError(
                f"resilience.chaos.n_schedules must be >= 1, got "
                f"{self.n_schedules}")
        if self.max_faults < 1:
            raise DeepSpeedConfigError(
                f"resilience.chaos.max_faults must be >= 1, got "
                f"{self.max_faults}")
        if not self.artifact_dir:
            raise DeepSpeedConfigError(
                "resilience.chaos.artifact_dir must be a non-empty path")


@dataclass
class ResilienceConfig:
    """Training resilience block (``resilience``; consumed by
    ``runtime/engine.py`` + ``resilience/guardrails.py``; docs/resilience.md).

    - ``enabled``: arm the host-side guardrail. The compiled step *always*
      skips non-finite updates (the loss-scale overflow path gates bf16/fp32
      too); this switch adds per-step host tracking of the overflow scalar —
      one scalar device fetch per step, which breaks the async step chain,
      so it is off by default and meant for production training jobs where
      a wedged run costs more than the sync.
    - ``max_consecutive_bad_steps``: streak length at which skipping is
      declared insufficient and the engine rewinds (or raises
      ``TrainingDivergedError`` when no rewind target exists).
    - ``rewind``: reload the last checkpoint saved outside a bad streak when
      the streak threshold is hit. Data-loader replay after a rewind is the
      caller's responsibility (the engine restores model/optimizer state and
      the step clock).
    - ``preemption``: signal-driven just-in-time checkpoints (its own
      dataclass above).
    - ``retry``: bounded-backoff policy wrapped around checkpoint saves
      (transient storage errors survive; permanent ones still surface).
    - ``fault_injection``: deterministic fault source for tests/CI smoke.
    - ``chaos``: seeded fault-space search over generated schedules (its
      own dataclass above).
    """

    enabled: bool = False
    max_consecutive_bad_steps: int = 3
    rewind: bool = True
    preemption: PreemptionConfig = field(default_factory=PreemptionConfig)
    retry: RetryConfig = field(default_factory=RetryConfig)
    fault_injection: FaultInjectionConfig = field(default_factory=FaultInjectionConfig)
    chaos: ChaosConfig = field(default_factory=ChaosConfig)

    def __post_init__(self):
        if isinstance(self.preemption, dict):
            self.preemption = _build(PreemptionConfig, self.preemption)
        if isinstance(self.retry, dict):
            self.retry = _build(RetryConfig, self.retry)
        if isinstance(self.fault_injection, dict):
            self.fault_injection = _build(FaultInjectionConfig, self.fault_injection)
        if isinstance(self.chaos, dict):
            self.chaos = _build(ChaosConfig, self.chaos)
        if self.max_consecutive_bad_steps < 1:
            raise DeepSpeedConfigError(
                "resilience.max_consecutive_bad_steps must be >= 1, got "
                f"{self.max_consecutive_bad_steps}")


@dataclass
class PrefixCacheConfig:
    """Serving prefix-cache block (``serving.prefix_cache``; docs/serving.md).

    RadixAttention-style prompt KV reuse: a host-side trie maps prompt token
    prefixes to slots of a device-side KV pool
    ``[L, n_slots, max_prefix_len, H, Dh]``; admission copies the longest
    cached prefix into the request's slot with one compiled program and
    prefills only the suffix.

    - ``enabled``: allocate the pool and consult the trie on every admission.
    - ``n_slots``: pool capacity (cached prefixes resident on device).
    - ``max_prefix_len``: pool window length (tokens per cached prefix);
      0 = the serving slot length. Longer windows reuse more but cost
      ``2 * L * n_slots * max_prefix_len * hidden`` bytes of HBM.
    - ``block``: trie granularity — prefixes are cached/matched in whole
      blocks of this many tokens.
    - ``insert_policy``: ``always`` caches every admitted prompt's prefix;
      ``min_hits`` caches a prefix only once ``min_hits`` prompts have
      shared it (one-off prompts never consume a pool slot).
    """

    enabled: bool = False
    n_slots: int = 8
    max_prefix_len: int = 0  # 0 = the serving slot length (Smax)
    block: int = 16
    insert_policy: str = "always"
    min_hits: int = 2

    def __post_init__(self):
        if self.insert_policy not in ("always", "min_hits"):
            raise DeepSpeedConfigError(
                f"serving.prefix_cache.insert_policy must be always|min_hits, "
                f"got {self.insert_policy!r}")
        if self.n_slots < 1:
            raise DeepSpeedConfigError(
                f"serving.prefix_cache.n_slots must be >= 1, got {self.n_slots}")
        if self.block < 1:
            raise DeepSpeedConfigError(
                f"serving.prefix_cache.block must be >= 1, got {self.block}")
        if self.min_hits < 1:
            # min_hits <= 0 would make the popularity bar vacuous — every
            # one-off prompt would cache on first traversal, silently
            # turning min_hits into always
            raise DeepSpeedConfigError(
                f"serving.prefix_cache.min_hits must be >= 1, got {self.min_hits}")


@dataclass
class ChunkedPrefillConfig:
    """Serving chunked-prefill block (``serving.chunked_prefill``;
    docs/serving.md). Sarathi-Serve-style admission: prompt suffixes are
    split into ``chunk_size``-token chunks run one per scheduler step,
    interleaved with decode — active slots never stall behind a long prompt
    for more than one chunk.

    - ``chunk_size``: tokens per chunk; must be a power of two (the
      remainder runs as one power-of-two-bucketed padded tail segment, so
      the compiled chunk-program set is {chunk_size, chunk_size/2, ...} — a
      handful of stable programs, never one per prompt length).
    - ``chunks_per_step``: prefill chunks advanced per scheduler step across
      all admitting requests (decode stall bound).
    """

    enabled: bool = False
    chunk_size: int = 64
    chunks_per_step: int = 1

    def __post_init__(self):
        c = self.chunk_size
        if c < 1 or (c & (c - 1)) != 0:
            raise DeepSpeedConfigError(
                f"serving.chunked_prefill.chunk_size must be a power of two, got {c}")
        if self.chunks_per_step < 1:
            raise DeepSpeedConfigError(
                f"serving.chunked_prefill.chunks_per_step must be >= 1, "
                f"got {self.chunks_per_step}")


@dataclass
class RouterHealthConfig:
    """``serving.router.health`` block (consumed by ``inference/router.py``;
    docs/serving.md "Multi-replica router").

    - ``timeout``: step-latency heartbeat bound (seconds). A replica whose
      scheduler step is observed past it gets a HUNG verdict; 0 disables
      the liveness check (steps are still timed for telemetry).
    - ``max_attempts`` / ``base_delay_s`` / ``max_delay_s`` / ``jitter``:
      the probation schedule, field-compatible with ``resilience.retry``'s
      ``RetryPolicy`` so ``resilience/retry.backoff_delay`` consumes this
      config directly. A hung replica is re-admitted after the backoff for
      its verdict count; the ``max_attempts``-th hung verdict escalates to
      DEAD (detached, like a crashed replica).
    """

    timeout: float = 5.0
    max_attempts: int = 3
    base_delay_s: float = 0.5
    max_delay_s: float = 8.0
    jitter: float = 0.25

    def __post_init__(self):
        if self.timeout < 0:
            raise DeepSpeedConfigError(
                f"serving.router.health.timeout must be >= 0, got {self.timeout}")
        if self.max_attempts < 1:
            raise DeepSpeedConfigError(
                f"serving.router.health.max_attempts must be >= 1, "
                f"got {self.max_attempts}")
        if self.base_delay_s < 0 or self.max_delay_s < 0:
            raise DeepSpeedConfigError(
                "serving.router.health delays must be >= 0")
        if not 0.0 <= self.jitter <= 1.0:
            raise DeepSpeedConfigError(
                f"serving.router.health.jitter must be in [0, 1], "
                f"got {self.jitter}")


@dataclass
class JournalConfig:
    """``serving.router.journal`` block (consumed by
    ``inference/journal.RequestJournal`` via ``inference/router.Router``;
    docs/serving.md "Crash-safe control plane").

    The durable request journal that makes a control-plane (router/gateway)
    crash a recoverable event: every ACCEPTED request is recorded (with its
    idempotency key), every terminal result and cancel is recorded, and a
    restarted Router replays the journal + reconciles against surviving
    workers to rebuild its owner map with zero accepted-request loss.

    - ``enabled``: write the journal and recover from it on cold start. A
      disabled fleet constructs NO journal and pays ZERO new fsyncs on the
      submit/terminal hot path.
    - ``path``: the journal file. Rotation/compaction rewrites it with the
      checkpoint saver's rename-durability discipline (tmp + fsync +
      rename + directory fsync).
    - ``fsync``: fsync after every appended record (the durability the
      recovery proof rests on). False trades crash-durability of the last
      few records for latency — replay still tolerates the torn tail.
    - ``rotate_max_records``: appended records between compactions; past it
      the journal is rewritten to live requests + retained terminals so an
      always-on fleet's journal stays bounded.
    - ``keep_terminals``: terminal records retained across compactions —
      the idempotent-replay window (a retried idempotency key older than
      this may be re-submitted as a fresh request).
    """

    enabled: bool = False
    path: str = ""
    fsync: bool = True
    rotate_max_records: int = 4096
    keep_terminals: int = 1024

    def __post_init__(self):
        if self.enabled and not self.path:
            raise DeepSpeedConfigError(
                "serving.router.journal.enabled requires journal.path")
        if self.rotate_max_records < 2:
            raise DeepSpeedConfigError(
                f"serving.router.journal.rotate_max_records must be >= 2, "
                f"got {self.rotate_max_records}")
        if self.keep_terminals < 0:
            raise DeepSpeedConfigError(
                f"serving.router.journal.keep_terminals must be >= 0, "
                f"got {self.keep_terminals}")


@dataclass
class RouterTransportConfig:
    """``serving.router.transport`` block (consumed by
    ``inference/rpc.ReplicaClient`` + ``launcher/serving_worker.
    WorkerSupervisor``; docs/serving.md "Process-mode deployment").

    Governs the RPC transport when replicas are worker processes (in-process
    replicas never touch it):

    - ``family``: ``unix`` (same-host socket files, the default) or ``tcp``
      (loopback/cross-host) — the SAME DSRP crc32 frames, per-call
      monotonic deadlines, bounded-backoff reconnect and replay-safe
      step/withdraw discipline ride both families.
    - ``host``: TCP bind/connect host for supervisor-spawned workers
      (``127.0.0.1`` for same-host fleets; a routable address for
      cross-host ones).
    - ``port_base``: TCP listen port for worker slot ``i`` is
      ``port_base + i``; 0 (the default) lets the OS assign an ephemeral
      port, which the supervisor learns from the worker's ``ready`` line —
      collision-free without coordination.
    - ``call_timeout_s``: per-call reply deadline. A ``step()`` that misses
      it surfaces as ``RpcTimeout`` — the Router's HUNG verdict (the call
      may have executed; the outcome is unknown).
    - ``connect_attempts`` / ``base_delay_s`` / ``max_delay_s`` / ``jitter``:
      the reconnect schedule, field-compatible with ``resilience.retry``'s
      ``RetryPolicy`` (``backoff_delay`` consumes it directly). A client
      whose connection dropped pays this bounded backoff on the next call.
    - ``boot_timeout_s``: how long the supervisor waits for a freshly
      spawned worker's socket to accept (covers interpreter + engine boot
      and cold XLA compiles).
    - ``heartbeat_timeout_s``: worker heartbeat-file staleness (judged on a
      monotonic clock) past which the supervisor SIGKILLs and respawns;
      0 disables heartbeat supervision (process exit is still detected).
    """

    family: str = "unix"
    host: str = "127.0.0.1"
    port_base: int = 0
    call_timeout_s: float = 30.0
    connect_attempts: int = 4
    base_delay_s: float = 0.2
    max_delay_s: float = 2.0
    jitter: float = 0.25
    boot_timeout_s: float = 60.0
    heartbeat_timeout_s: float = 10.0

    def __post_init__(self):
        if self.family not in ("unix", "tcp"):
            raise DeepSpeedConfigError(
                f"serving.router.transport.family must be unix|tcp, "
                f"got {self.family!r}")
        if not 0 <= self.port_base <= 65535:
            raise DeepSpeedConfigError(
                f"serving.router.transport.port_base must be in [0, 65535], "
                f"got {self.port_base}")
        if self.call_timeout_s <= 0:
            raise DeepSpeedConfigError(
                f"serving.router.transport.call_timeout_s must be > 0, "
                f"got {self.call_timeout_s}")
        if self.connect_attempts < 1:
            raise DeepSpeedConfigError(
                f"serving.router.transport.connect_attempts must be >= 1, "
                f"got {self.connect_attempts}")
        if self.base_delay_s < 0 or self.max_delay_s < 0:
            raise DeepSpeedConfigError(
                "serving.router.transport delays must be >= 0")
        if not 0.0 <= self.jitter <= 1.0:
            raise DeepSpeedConfigError(
                f"serving.router.transport.jitter must be in [0, 1], "
                f"got {self.jitter}")
        if self.boot_timeout_s <= 0:
            raise DeepSpeedConfigError(
                f"serving.router.transport.boot_timeout_s must be > 0, "
                f"got {self.boot_timeout_s}")
        if self.heartbeat_timeout_s < 0:
            raise DeepSpeedConfigError(
                f"serving.router.transport.heartbeat_timeout_s must be "
                f">= 0, got {self.heartbeat_timeout_s}")


@dataclass
class AutoscaleConfig:
    """``serving.router.autoscale`` block (consumed by
    ``inference/autoscaler.Autoscaler``; docs/serving.md "Elastic fleet &
    brownout").

    Closes the loop from the fleet's own telemetry (router load, arrival
    backlog, per-replica step latency, PR 7's MFU gauges) back to
    ``attach_replica``/``drain_replica`` — with hysteresis so a flapping
    metric can never oscillate the fleet:

    - ``enabled``: evaluate scaling on every router step (an in-process
      ``Router(engine, config=...)`` builds its own autoscaler; a
      process-mode fleet wires one to a ``WorkerSupervisor``).
    - ``min_replicas`` / ``max_replicas``: the fleet-size envelope.
    - ``scale_up_queue``: fleet-wide queued-request backlog at/past which
      the up-signal fires.
    - ``scale_up_load``: mean scheduler load per HEALTHY replica
      (queued + prefilling + decoding) at/past which the up-signal fires.
    - ``scale_up_step_s``: last observed per-replica step latency past
      which the up-signal fires (0 disables the latency signal).
    - ``scale_up_mfu``: mean fleet MFU (from the program ledger's
      ``serving/mfu`` gauges, observed through ``Router.
      telemetry_snapshot()``) at/past which the up-signal fires — a
      compute-saturated fleet scales out even before queues grow
      (0 disables; unrated platforms never produce the gauge).
    - ``scale_down_load``: mean load per healthy replica at/below which
      (with an empty backlog) the down-signal fires; must not exceed
      ``scale_up_load`` or flapping is guaranteed.
    - ``up_consecutive`` / ``down_consecutive``: evaluations the signal
      must persist before acting (the hysteresis window).
    - ``cooldown_s``: minimum router-clock seconds between scale actions.
    - ``brownout_deadline_s``: deadline applied to deadline-free requests
      while the fleet is browned out (at max and still saturated);
      0 = never tighten deadlines.
    - ``events_capacity``: bounded ring of typed autoscale decision events
      (rendered by the report CLI, carried in snapshots).
    """

    enabled: bool = False
    min_replicas: int = 1
    max_replicas: int = 4
    scale_up_queue: int = 4
    scale_up_load: float = 3.0
    scale_up_step_s: float = 0.0
    scale_up_mfu: float = 0.0
    scale_down_load: float = 0.5
    up_consecutive: int = 2
    down_consecutive: int = 4
    cooldown_s: float = 5.0
    brownout_deadline_s: float = 0.0
    events_capacity: int = 256

    def __post_init__(self):
        if self.min_replicas < 1:
            raise DeepSpeedConfigError(
                f"serving.router.autoscale.min_replicas must be >= 1, "
                f"got {self.min_replicas}")
        if self.max_replicas < self.min_replicas:
            raise DeepSpeedConfigError(
                f"serving.router.autoscale.max_replicas ({self.max_replicas}) "
                f"must be >= min_replicas ({self.min_replicas})")
        if self.up_consecutive < 1 or self.down_consecutive < 1:
            raise DeepSpeedConfigError(
                "serving.router.autoscale up/down_consecutive must be >= 1")
        if self.cooldown_s < 0 or self.brownout_deadline_s < 0:
            raise DeepSpeedConfigError(
                "serving.router.autoscale cooldown_s/brownout_deadline_s "
                "must be >= 0")
        if (self.scale_up_queue < 0 or self.scale_up_load < 0
                or self.scale_up_step_s < 0 or self.scale_down_load < 0
                or not 0.0 <= self.scale_up_mfu <= 1.0):
            raise DeepSpeedConfigError(
                "serving.router.autoscale thresholds must be >= 0 "
                "(scale_up_mfu in [0, 1])")
        if 0 < self.scale_up_load < self.scale_down_load:
            # a down threshold above the up threshold makes one load value
            # simultaneously an up- and down-signal: guaranteed flapping
            # (scale_up_load 0 disables the load up-signal entirely, so no
            # flap is possible from it)
            raise DeepSpeedConfigError(
                f"serving.router.autoscale.scale_down_load "
                f"({self.scale_down_load}) must be <= scale_up_load "
                f"({self.scale_up_load})")
        if self.events_capacity < 1:
            raise DeepSpeedConfigError(
                f"serving.router.autoscale.events_capacity must be >= 1, "
                f"got {self.events_capacity}")


@dataclass
class DisaggConfig:
    """``serving.router.disagg`` block (consumed by
    ``inference/router.Router`` + ``inference/autoscaler.Autoscaler``;
    docs/serving.md "Disaggregated prefill/decode").

    Splits the fleet into a PREFILL pool (admission + chunked prefill, then
    a streamed KV handoff) and a DECODE pool (decode/speculation/SSE
    progress) behind the same Router, because the two phases saturate
    different resources (prefill: compute; decode: HBM bandwidth):

    - ``enabled``: role-aware dispatch + per-request KV handoff state
      machine. Off = every replica runs both phases (the co-located fleet).
    - ``prefill_replicas`` / ``decode_replicas``: initial pool sizes for an
      in-process disaggregated fleet (process-mode fleets size pools by the
      roles their supervisor assigns).
    - ``handoff_chunk``: KV wire-window width per export/import call — a
      power of two in [8, 128], so the compiled ``kv_export``/``kv_import``
      program families stay pow2-bounded exactly like chunked prefill.
    - ``kv_compression``: ``none`` (bitwise-exact handoff, the default) or
      ``int8`` (per-call absmax quantization on the wire — ~4x fewer
      bytes, a bounded rounding error documented in docs/serving.md;
      greedy parity is no longer bitwise).
    - ``prefill_min_replicas`` / ``prefill_max_replicas`` and
      ``decode_min_replicas`` / ``decode_max_replicas``: per-pool fleet
      envelopes for the autoscaler (each pool scales on its OWN signals).
    - ``prefill_scale_up_queue``: pool-wide arrived-request backlog at/past
      which the prefill up-signal fires.
    - ``prefill_scale_up_backlog``: pool-wide chunk backlog (slots mid-
      prefill + finished slots parked awaiting handoff) at/past which the
      prefill up-signal fires.
    - ``decode_scale_up_occupancy``: mean decode-slot occupancy fraction
      at/past which the decode up-signal fires.
    - ``decode_scale_up_step_s``: decode-replica step latency past which
      the decode up-signal fires (0 disables the latency signal).

    Scale-down, hysteresis (``up_consecutive``/``down_consecutive``),
    ``cooldown_s`` and the events ring reuse the ``autoscale`` block —
    disagg only splits the SIGNALS and the min/max envelopes per pool.
    """

    enabled: bool = False
    prefill_replicas: int = 1
    decode_replicas: int = 1
    handoff_chunk: int = 64
    kv_compression: str = "none"
    prefill_min_replicas: int = 1
    prefill_max_replicas: int = 4
    decode_min_replicas: int = 1
    decode_max_replicas: int = 4
    prefill_scale_up_queue: int = 4
    prefill_scale_up_backlog: int = 4
    decode_scale_up_occupancy: float = 0.75
    decode_scale_up_step_s: float = 0.0

    def __post_init__(self):
        if self.prefill_replicas < 1 or self.decode_replicas < 1:
            raise DeepSpeedConfigError(
                "serving.router.disagg prefill_replicas/decode_replicas "
                "must be >= 1")
        w = self.handoff_chunk
        if w < 8 or w > 128 or (w & (w - 1)) != 0:
            raise DeepSpeedConfigError(
                f"serving.router.disagg.handoff_chunk must be a power of "
                f"two in [8, 128], got {w}")
        if self.kv_compression not in ("none", "int8"):
            raise DeepSpeedConfigError(
                f"serving.router.disagg.kv_compression must be none|int8, "
                f"got {self.kv_compression!r}")
        if self.prefill_min_replicas < 1 or self.decode_min_replicas < 1:
            raise DeepSpeedConfigError(
                "serving.router.disagg per-pool min replicas must be >= 1")
        if (self.prefill_max_replicas < self.prefill_min_replicas
                or self.decode_max_replicas < self.decode_min_replicas):
            raise DeepSpeedConfigError(
                "serving.router.disagg per-pool max replicas must be >= "
                "the pool's min replicas")
        if (self.prefill_scale_up_queue < 0
                or self.prefill_scale_up_backlog < 0
                or self.decode_scale_up_step_s < 0
                or not 0.0 <= self.decode_scale_up_occupancy <= 1.0):
            raise DeepSpeedConfigError(
                "serving.router.disagg scale thresholds must be >= 0 "
                "(decode_scale_up_occupancy in [0, 1])")


@dataclass
class TenantConfig:
    """One tenant under ``serving.gateway.auth.tenants`` (consumed by
    ``launcher/http_gateway.HttpGateway`` + ``inference/router.Router`` +
    ``inference/serving.ServingEngine``; docs/serving.md "Multi-tenant
    isolation").

    - ``token_sha256``: hex SHA-256 digest of the tenant's bearer token.
      The RAW token never appears in config files the fleet journals or
      snapshots — the gateway compares ``sha256(presented)`` against this
      digest with a constant-time compare, so neither logs, journals,
      traces nor ``/metrics`` can ever leak the credential.
    - ``weight``: deficit-weighted-round-robin share of admission
      bandwidth (relative to other tenants with queued work).
    - ``max_queued``: per-tenant bound on arrived not-yet-admitted
      requests across the fleet; past it submits bounce with a typed
      ``RequestRejected(reason="tenant_quota")`` → HTTP 429. 0 =
      unbounded (the tenant still competes under its DWRR weight).
    - ``rate_rps`` / ``burst``: token-bucket rate limit at the gateway —
      sustained requests/second and the bucket depth. ``rate_rps`` 0
      disables the bucket.
    """

    token_sha256: str = ""
    weight: float = 1.0
    max_queued: int = 0
    rate_rps: float = 0.0
    burst: int = 8

    def __post_init__(self):
        if self.weight < 0.01:
            raise DeepSpeedConfigError(
                f"serving.gateway.auth tenant weight must be >= 0.01, "
                f"got {self.weight}")
        if self.max_queued < 0 or self.rate_rps < 0:
            raise DeepSpeedConfigError(
                "serving.gateway.auth tenant max_queued/rate_rps must be "
                ">= 0")
        if self.burst < 1:
            raise DeepSpeedConfigError(
                f"serving.gateway.auth tenant burst must be >= 1, "
                f"got {self.burst}")
        d = self.token_sha256
        if d and (len(d) != 64 or any(c not in "0123456789abcdef"
                                      for c in d.lower())):
            raise DeepSpeedConfigError(
                "serving.gateway.auth tenant token_sha256 must be a "
                "64-char hex SHA-256 digest (never the raw token)")


@dataclass
class GatewayAuthConfig:
    """``serving.gateway.auth`` block (docs/serving.md "Multi-tenant
    isolation").

    - ``enabled``: require ``Authorization: Bearer <token>`` on
      ``POST /v1/generate``. Missing/malformed credentials → 401; a token
      matching no tenant digest → 403. Off = every request is the
      anonymous tenant ``""`` (the single-tenant behavior).
    - ``tenants``: tenant id → ``TenantConfig`` (weight / quota / rate
      limits keyed by the SHA-256 digest of each tenant's bearer token).
      Tenant ids are plain printable identifiers (no control characters —
      they ride metric names and journal records).
    """

    enabled: bool = False
    tenants: dict = field(default_factory=dict)

    def __post_init__(self):
        coerced = {}
        for tid, block in (self.tenants or {}).items():
            if not tid or any(ord(c) < 0x20 or c == "\x7f" for c in tid):
                raise DeepSpeedConfigError(
                    f"serving.gateway.auth.tenants id {tid!r} must be a "
                    f"non-empty string without control characters")
            coerced[tid] = (_build(TenantConfig, block)
                            if isinstance(block, dict) else block)
        self.tenants = coerced
        if self.enabled and not self.tenants:
            raise DeepSpeedConfigError(
                "serving.gateway.auth.enabled requires at least one "
                "entry in serving.gateway.auth.tenants")
        if self.enabled:
            for tid, t in self.tenants.items():
                if not t.token_sha256:
                    raise DeepSpeedConfigError(
                        f"serving.gateway.auth tenant {tid!r} needs a "
                        f"token_sha256 digest when auth is enabled")


@dataclass
class GatewayConfig:
    """``serving.gateway`` block (consumed by
    ``launcher/http_gateway.HttpGateway``; docs/serving.md "HTTP front door
    & rolling upgrades").

    - ``enabled``: serve the Router over the HTTP/SSE front door (ignored
      by code that constructs ``HttpGateway`` directly — drills and tests
      pass the block explicitly).
    - ``host``: listen address (``127.0.0.1`` for same-host clients; a
      routable address to face real traffic).
    - ``port``: listen port; 0 (the default) binds an OS-assigned ephemeral
      port, resolved at start and exposed as ``HttpGateway.port``.
    - ``stream_poll_s``: how long an idle SSE stream waits for new tokens
      before re-checking its feed (also the serve loop's idle pace). Lower
      = lower token latency, higher host spin.
    - ``write_timeout_s``: per-send socket deadline on streaming responses.
      A reader that stops draining its socket (slow-reader stall) blocks the
      server's send past this budget and is treated as a DISCONNECT — the
      request is cancelled, its slot freed. 0 disables (an undeadlined
      write can hang a handler thread forever — keep it > 0 in production).
    - ``retry_after_s``: the ``Retry-After`` hint on 429/503 responses;
      0 derives it from the autoscaler's ``cooldown_s`` (the earliest
      instant more capacity could exist) with a 1s floor.
    - ``max_body_bytes``: request-body bound; larger POSTs are rejected 413
      before parsing (a gateway must not buffer unbounded client bytes).
    - ``shutdown_grace_s``: how long a SIGTERM drain waits for in-flight
      streams to finish before closing their connections anyway (0 =
      unbounded — trust the deadline machinery underneath).
    - ``metrics_fleet_refresh_s``: serve-loop cadence for refreshing the
      cached fleet telemetry snapshot that ``GET /metrics`` renders with
      per-replica labels (the loop owns the RPC sockets; handler threads
      only read the cache). 0 = off — ``/metrics`` exports the gateway's
      local registry only.
    - ``auth``: multi-tenant bearer auth + fairness sub-block (its own
      dataclass above; docs/serving.md "Multi-tenant isolation").
    """

    enabled: bool = False
    host: str = "127.0.0.1"
    port: int = 0
    stream_poll_s: float = 0.05
    write_timeout_s: float = 10.0
    retry_after_s: float = 0.0
    max_body_bytes: int = 1 << 20
    shutdown_grace_s: float = 30.0
    metrics_fleet_refresh_s: float = 0.0
    auth: GatewayAuthConfig = field(default_factory=GatewayAuthConfig)

    def __post_init__(self):
        if isinstance(self.auth, dict):
            self.auth = _build(GatewayAuthConfig, self.auth)
        if not 0 <= self.port <= 65535:
            raise DeepSpeedConfigError(
                f"serving.gateway.port must be in [0, 65535], got {self.port}")
        if self.stream_poll_s <= 0:
            raise DeepSpeedConfigError(
                f"serving.gateway.stream_poll_s must be > 0, "
                f"got {self.stream_poll_s}")
        if self.write_timeout_s < 0 or self.retry_after_s < 0 \
                or self.shutdown_grace_s < 0 \
                or self.metrics_fleet_refresh_s < 0:
            raise DeepSpeedConfigError(
                "serving.gateway write_timeout_s/retry_after_s/"
                "shutdown_grace_s/metrics_fleet_refresh_s must be >= 0")
        if self.max_body_bytes < 1:
            raise DeepSpeedConfigError(
                f"serving.gateway.max_body_bytes must be >= 1, "
                f"got {self.max_body_bytes}")


@dataclass
class SpeculationConfig:
    """``serving.speculation`` block (consumed by
    ``inference/serving.ServingEngine`` + ``inference/speculation.py``;
    docs/serving.md "Speculative decoding").

    Self-speculative multi-token decoding: a host-side n-gram /
    prompt-lookup drafter (Saxena 2023 — no draft model) proposes up to
    ``depth`` tokens per slot from the request's own prompt+output history,
    and a bounded pow2-bucketed family of compiled verify programs scores
    the whole draft in ONE forward pass (Leviathan et al. 2023). Greedy
    requests keep bitwise parity with non-speculative decode.

    - ``enabled``: draft + verify on the serving decode path. Off = the
      legacy one-token decode program, untouched.
    - ``depth``: max draft tokens proposed per slot per step. The verify
      program set is {1, 2, 4, ..., next_pow2(depth)} — bounded like the
      chunked-prefill width family, never one program per draft length.
    - ``ngram_min_match``: smallest history suffix (tokens) that must
      re-occur earlier in prompt+output before the drafter proposes its
      continuation. Higher = fewer, higher-confidence drafts.
    - ``draft_source``: ``ngram`` (the host-side self-drafter) or
      ``draft_model`` (EXPERIMENTAL: a host-resident tiny draft model —
      deterministic, seeded from the serving seed; greedy parity still
      holds because verification, not the draft, decides every token).
    """

    enabled: bool = False
    depth: int = 4
    ngram_min_match: int = 2
    draft_source: str = "ngram"

    def __post_init__(self):
        if self.draft_source not in ("ngram", "draft_model"):
            raise DeepSpeedConfigError(
                f"serving.speculation.draft_source must be ngram|draft_model, "
                f"got {self.draft_source!r}")
        if self.depth < 1:
            raise DeepSpeedConfigError(
                f"serving.speculation.depth must be >= 1, got {self.depth}")
        if self.ngram_min_match < 1:
            raise DeepSpeedConfigError(
                f"serving.speculation.ngram_min_match must be >= 1, "
                f"got {self.ngram_min_match}")


BLOCK_STRATEGIES = ("low_confidence_static", "low_confidence_dynamic")


@dataclass
class BlockGenerationConfig:
    """``serving.block_generation`` block (consumed by
    ``inference/serving.ServingEngine`` for a model that generates by diffusion
    over blocks, ``attn_block_length`` > 1; docs/serving.md "Generation by
    diffusion over blocks"). The deployment's choice of quality against speed;
    nothing of it is baked into a compiled program.

    - ``denoising_steps``: T, the denoising passes a block of B positions takes
      (1 .. B; 0 = B). A pass reveals B // T of the block's masked positions,
      the last what is left; one more pass, the commit, writes the finished
      block's K/V.
    - ``strategy``: ``low_confidence_static`` (the B // T masked rows of largest
      confidence a pass: the host knows every slot's pass without a fetch, so a
      block step is enqueued ahead of the last one's fetch) or
      ``low_confidence_dynamic`` (besides, every masked row whose confidence is
      over ``threshold``: the host fetches a step before it plans the next).
    - ``threshold``: the dynamic strategy's confidence above which a row is
      revealed whatever its rank. The default cites no source (the family's own
      sampler is not in this repository) and the dynamic path has no chip reading:
      docs/serving.md.
    """

    denoising_steps: int = 0
    strategy: str = "low_confidence_static"
    threshold: float = 0.9

    def __post_init__(self):
        if self.strategy not in BLOCK_STRATEGIES:
            raise DeepSpeedConfigError(
                f"serving.block_generation.strategy must be one of {BLOCK_STRATEGIES}, "
                f"got {self.strategy!r}")
        if self.denoising_steps < 0:
            raise DeepSpeedConfigError(
                f"serving.block_generation.denoising_steps must be >= 0, "
                f"got {self.denoising_steps}")


@dataclass
class RouterConfig:
    """``serving.router`` block (consumed by ``inference/router.Router``;
    docs/serving.md "Multi-replica router").

    - ``replicas``: ``ServingEngine`` replicas behind the router. 1 keeps
      the single-engine behavior (the router is then a thin pass-through).
    - ``affinity``: prefix-affinity dispatch — prefer the replica whose
      radix trie already holds the longest match of the prompt (stat-free
      peek), falling back to least-loaded. Only meaningful with
      ``serving.prefix_cache.enabled``.
    - ``max_queue_len``: GLOBAL bound on arrived not-yet-admitted requests
      summed across live replicas; past it ``submit`` raises a typed
      ``RequestRejected(reason="queue_full")``. 0 = unbounded. Per-replica
      ``serving.max_queue_len`` still applies underneath.
    - ``health``: liveness/probation sub-block (its own dataclass above).
    - ``transport``: RPC transport sub-block for process-mode replicas
      (its own dataclass above; ignored by in-process fleets).
    - ``autoscale``: ledger-driven elastic scaling sub-block (its own
      dataclass above; docs/serving.md "Elastic fleet & brownout").
    - ``disagg``: disaggregated prefill/decode sub-block (its own dataclass
      above; docs/serving.md "Disaggregated prefill/decode").
    - ``journal``: durable request-journal sub-block (its own dataclass
      above; docs/serving.md "Crash-safe control plane").
    """

    replicas: int = 1
    affinity: bool = True
    max_queue_len: int = 0
    health: RouterHealthConfig = field(default_factory=RouterHealthConfig)
    transport: RouterTransportConfig = field(
        default_factory=RouterTransportConfig)
    autoscale: AutoscaleConfig = field(default_factory=AutoscaleConfig)
    disagg: DisaggConfig = field(default_factory=DisaggConfig)
    journal: JournalConfig = field(default_factory=JournalConfig)

    def __post_init__(self):
        if isinstance(self.health, dict):
            self.health = _build(RouterHealthConfig, self.health)
        if isinstance(self.transport, dict):
            self.transport = _build(RouterTransportConfig, self.transport)
        if isinstance(self.autoscale, dict):
            self.autoscale = _build(AutoscaleConfig, self.autoscale)
        if isinstance(self.disagg, dict):
            self.disagg = _build(DisaggConfig, self.disagg)
        if isinstance(self.journal, dict):
            self.journal = _build(JournalConfig, self.journal)
        if self.replicas < 1:
            raise DeepSpeedConfigError(
                f"serving.router.replicas must be >= 1, got {self.replicas}")
        if self.max_queue_len < 0:
            raise DeepSpeedConfigError(
                f"serving.router.max_queue_len must be >= 0, "
                f"got {self.max_queue_len}")


@dataclass
class ServingConfig:
    """Serving-engine block (``serving``; consumed by
    ``deepspeed_tpu.inference.ServingEngine``, docs/serving.md).

    Degradation knobs (docs/resilience.md):

    - ``max_queue_len``: bound on *arrived* not-yet-admitted requests; when
      exceeded the newest arrivals are load-shed with a typed
      ``RequestRejected(reason="queue_full")`` / ``shed_queue_full`` result
      instead of growing the queue without bound. 0 = unbounded.
    - ``default_deadline_s``: deadline (seconds after arrival) applied to
      requests that do not carry their own; past it a queued request is shed
      (``expired``) and an in-flight one is cancelled mid-prefill or evicted
      mid-decode with its partial output (``deadline_exceeded``). 0 = none.
    - ``quarantine_max_requeues``: times a request whose logits went
      non-finite is re-queued for a clean replay before being failed
      (``failed_nan``).
    - ``slot_quarantine_after``: consecutive NaN-logit faults in one slot
      after which that slot is pulled from rotation (suspected bad hardware
      lane); the last healthy slot is never quarantined.
    - ``tenants``: tenant id → ``TenantConfig``-shaped block (``weight`` /
      ``max_queued``; the auth fields are gateway-side and ignored here).
      Drives the engine scheduler's deficit-weighted round-robin admission
      and per-tenant queue caps (docs/serving.md "Multi-tenant
      isolation"). Empty = single-tenant FIFO-equivalent behavior.
    """

    n_slots: int = 8
    max_seq_len: int = 0  # 0 = the engine's sequence budget
    min_prefill_bucket: int = 16
    seed: int = 0
    jsonl_path: str = ""
    watchdog_mode: str = "warn"
    max_queue_len: int = 0  # 0 = unbounded
    default_deadline_s: float = 0.0  # 0 = no deadline
    quarantine_max_requeues: int = 1
    slot_quarantine_after: int = 2
    tenants: dict = field(default_factory=dict)
    prefix_cache: PrefixCacheConfig = field(default_factory=PrefixCacheConfig)
    chunked_prefill: ChunkedPrefillConfig = field(default_factory=ChunkedPrefillConfig)
    speculation: SpeculationConfig = field(default_factory=SpeculationConfig)
    block_generation: BlockGenerationConfig = field(default_factory=BlockGenerationConfig)
    fault_injection: FaultInjectionConfig = field(default_factory=FaultInjectionConfig)
    router: RouterConfig = field(default_factory=RouterConfig)
    gateway: GatewayConfig = field(default_factory=GatewayConfig)
    # observability sub-blocks (same schema as telemetry.ledger /
    # telemetry.request_trace — the serving engine owns its own Telemetry)
    ledger: LedgerConfig = field(default_factory=LedgerConfig)
    request_trace: RequestTraceConfig = field(default_factory=RequestTraceConfig)
    timeseries: TimeSeriesConfig = field(default_factory=TimeSeriesConfig)
    slo: SLOConfig = field(default_factory=SLOConfig)
    incidents: IncidentConfig = field(default_factory=IncidentConfig)
    jsonl_max_bytes: int = 0
    jsonl_keep: int = 3

    def __post_init__(self):
        if isinstance(self.tenants, dict):
            self.tenants = {
                tid: (_build(TenantConfig, block)
                      if isinstance(block, dict) else block)
                for tid, block in self.tenants.items()}
        if isinstance(self.prefix_cache, dict):
            self.prefix_cache = _build(PrefixCacheConfig, self.prefix_cache)
        if isinstance(self.chunked_prefill, dict):
            self.chunked_prefill = _build(ChunkedPrefillConfig, self.chunked_prefill)
        if isinstance(self.speculation, dict):
            self.speculation = _build(SpeculationConfig, self.speculation)
        if isinstance(self.block_generation, dict):
            self.block_generation = _build(BlockGenerationConfig, self.block_generation)
        if isinstance(self.fault_injection, dict):
            self.fault_injection = _build(FaultInjectionConfig, self.fault_injection)
        if isinstance(self.router, dict):
            self.router = _build(RouterConfig, self.router)
        if isinstance(self.gateway, dict):
            self.gateway = _build(GatewayConfig, self.gateway)
        if isinstance(self.ledger, dict):
            self.ledger = _build(LedgerConfig, self.ledger)
        if isinstance(self.request_trace, dict):
            self.request_trace = _build(RequestTraceConfig, self.request_trace)
        if isinstance(self.timeseries, dict):
            self.timeseries = _build(TimeSeriesConfig, self.timeseries)
        if isinstance(self.slo, dict):
            self.slo = _build(SLOConfig, self.slo)
        if isinstance(self.incidents, dict):
            self.incidents = _build(IncidentConfig, self.incidents)
        if self.jsonl_max_bytes < 0:
            raise DeepSpeedConfigError(
                f"serving.jsonl_max_bytes must be >= 0, "
                f"got {self.jsonl_max_bytes}")
        if self.jsonl_keep < 1:
            raise DeepSpeedConfigError(
                f"serving.jsonl_keep must be >= 1, got {self.jsonl_keep}")
        if self.watchdog_mode not in ("off", "warn", "raise"):
            raise DeepSpeedConfigError(
                f"serving.watchdog_mode must be off|warn|raise, "
                f"got {self.watchdog_mode!r}")
        if self.max_queue_len < 0:
            raise DeepSpeedConfigError(
                f"serving.max_queue_len must be >= 0, got {self.max_queue_len}")
        if self.default_deadline_s < 0:
            raise DeepSpeedConfigError(
                f"serving.default_deadline_s must be >= 0, "
                f"got {self.default_deadline_s}")
        if self.quarantine_max_requeues < 0:
            raise DeepSpeedConfigError(
                f"serving.quarantine_max_requeues must be >= 0, "
                f"got {self.quarantine_max_requeues}")
        if self.slot_quarantine_after < 1:
            raise DeepSpeedConfigError(
                f"serving.slot_quarantine_after must be >= 1, "
                f"got {self.slot_quarantine_after}")


@dataclass
class CurriculumConfig:
    """reference: runtime/data_pipeline/curriculum_scheduler.py:8."""

    enabled: bool = False
    curriculum_type: str = "seqlen"
    min_difficulty: int = 8
    max_difficulty: int = 1024
    schedule_type: str = "fixed_linear"
    schedule_config: dict = field(default_factory=dict)


@dataclass
class ProgressiveLayerDropConfig:
    enabled: bool = False
    theta: float = 0.5
    gamma: float = 0.001


@dataclass
class EigenvalueConfig:
    enabled: bool = False
    verbose: bool = False
    max_iter: int = 100
    tol: float = 1e-2
    stability: float = 1e-6
    gas_boundary_resolution: int = 1
    layer_name: str = "bert.encoder.layer"
    layer_num: int = 0


@dataclass
class AioConfig:
    """reference: runtime/swap_tensor/aio_config.py."""

    block_size: int = 1048576
    queue_depth: int = 8
    thread_count: int = 1
    single_submit: bool = False
    overlap_events: bool = True


@dataclass
class SparseAttentionConfig:
    """reference: runtime/config.py:283-466 sparse attention modes."""

    mode: str = "fixed"
    block: int = 16
    different_layout_per_head: bool = False
    num_local_blocks: int = 4
    num_global_blocks: int = 1
    attention: str = "bidirectional"
    horizontal_global_attention: bool = False
    num_different_global_patterns: int = 1
    num_random_blocks: int = 0
    local_window_blocks: list = field(default_factory=lambda: [4])
    global_block_indices: list = field(default_factory=lambda: [0])
    global_block_end_indices: Optional[list] = None
    num_sliding_window_blocks: int = 3


@dataclass
class MeshAxesConfig:
    """TPU-only: logical mesh shape. -1 = remainder (at most one axis)."""

    pipe: int = 1
    data: int = -1
    fsdp: int = 1
    context: int = 1
    model: int = 1


@dataclass
class CheckpointConfig:
    """``checkpoint`` block. ``keep_last_k > 0`` prunes older tags after
    each save (the 'latest'-pointed tag, the newest save, and the
    guardrail's last-good rewind target are always kept); 0 keeps all.
    ``verify_integrity=False`` skips the digest pass on load (it reads
    every checkpoint byte before the mmap'd restore — worth skipping for
    huge checkpoints on trusted storage); torn-checkpoint *detection* and
    fallback then rest on manifest presence alone."""

    tag_validation: str = "Warn"  # Ignore | Warn | Fail
    load_universal: bool = False
    use_node_local_storage: bool = False
    parallel_write_pipeline: bool = False
    engine: Optional[str] = None  # native | orbax (None = native)
    async_save: bool = False
    keep_last_k: int = 0  # 0 = keep every checkpoint
    verify_integrity: bool = True  # digest-check files before load

    def __post_init__(self):
        if self.keep_last_k < 0:
            raise DeepSpeedConfigError(
                f"checkpoint.keep_last_k must be >= 0, got {self.keep_last_k}")


@dataclass
class ElasticityConfig:
    """reference: elasticity/config.py + elasticity.py:287."""

    enabled: bool = False
    max_train_batch_size: int = 2000
    micro_batch_sizes: list = field(default_factory=lambda: [2, 4, 6])
    min_gpus: int = 1
    max_gpus: int = 10000
    min_time: int = 0
    version: float = 0.1
    ignore_non_elastic_batch_info: bool = False
    prefer_larger_batch: bool = True
    model_parallel_size: int = 1
    num_gpus_per_node: int = 1


@dataclass
class DeepSpeedConfig:
    """Top-level typed config. Entry point: ``DeepSpeedConfig.from_dict`` /
    ``from_file`` (reference ctor runtime/config.py:755 takes json path/dict).
    """

    train_batch_size: Optional[int] = None
    train_micro_batch_size_per_gpu: Optional[int] = None
    gradient_accumulation_steps: Optional[int] = None
    steps_per_print: int = C.STEPS_PER_PRINT_DEFAULT
    seed: int = C.SEED_DEFAULT
    gradient_clipping: float = C.GRADIENT_CLIPPING_DEFAULT
    prescale_gradients: bool = False
    gradient_predivide_factor: float = 1.0
    sparse_gradients: bool = False
    dataloader_drop_last: bool = False
    wall_clock_breakdown: bool = False
    memory_breakdown: bool = False
    dump_state: bool = False

    fp16: FP16Config = field(default_factory=FP16Config)
    bf16: BF16Config = field(default_factory=BF16Config)
    zero_optimization: ZeroConfig = field(default_factory=ZeroConfig)
    optimizer: OptimizerConfig = field(default_factory=OptimizerConfig)
    scheduler: SchedulerConfig = field(default_factory=SchedulerConfig)
    activation_checkpointing: ActivationCheckpointingConfig = field(default_factory=ActivationCheckpointingConfig)
    flops_profiler: FlopsProfilerConfig = field(default_factory=FlopsProfilerConfig)
    comms_logger: CommsLoggerConfig = field(default_factory=CommsLoggerConfig)
    tensorboard: MonitorBackendConfig = field(default_factory=MonitorBackendConfig)
    wandb: MonitorBackendConfig = field(default_factory=MonitorBackendConfig)
    csv_monitor: MonitorBackendConfig = field(default_factory=MonitorBackendConfig)
    telemetry: TelemetryConfig = field(default_factory=TelemetryConfig)
    serving: ServingConfig = field(default_factory=ServingConfig)
    resilience: ResilienceConfig = field(default_factory=ResilienceConfig)
    curriculum_learning: CurriculumConfig = field(default_factory=CurriculumConfig)
    progressive_layer_drop: ProgressiveLayerDropConfig = field(default_factory=ProgressiveLayerDropConfig)
    eigenvalue: EigenvalueConfig = field(default_factory=EigenvalueConfig)
    aio: AioConfig = field(default_factory=AioConfig)
    sparse_attention: Optional[SparseAttentionConfig] = None
    mesh: MeshAxesConfig = field(default_factory=MeshAxesConfig)
    checkpoint: CheckpointConfig = field(default_factory=CheckpointConfig)
    elasticity: ElasticityConfig = field(default_factory=ElasticityConfig)
    debug: DebugConfig = field(default_factory=DebugConfig)

    raw: dict = field(default_factory=dict, repr=False)

    # ------------------------------------------------------------------
    @classmethod
    def from_file(cls, path: str, world_size: int = 1) -> "DeepSpeedConfig":
        with open(path) as f:
            return cls.from_dict(json.load(f), world_size=world_size)

    @classmethod
    def from_dict(cls, d: dict, world_size: int = 1) -> "DeepSpeedConfig":
        cfg = cls(
            train_batch_size=d.get(C.TRAIN_BATCH_SIZE),
            train_micro_batch_size_per_gpu=d.get(C.TRAIN_MICRO_BATCH_SIZE_PER_GPU),
            gradient_accumulation_steps=d.get(C.GRADIENT_ACCUMULATION_STEPS),
            steps_per_print=d.get(C.STEPS_PER_PRINT, C.STEPS_PER_PRINT_DEFAULT),
            seed=int(d.get(C.SEED, C.SEED_DEFAULT)),
            gradient_clipping=d.get(C.GRADIENT_CLIPPING, C.GRADIENT_CLIPPING_DEFAULT),
            prescale_gradients=d.get(C.PRESCALE_GRADIENTS, False),
            gradient_predivide_factor=d.get(C.GRADIENT_PREDIVIDE_FACTOR, 1.0),
            sparse_gradients=d.get(C.SPARSE_GRADIENTS, False),
            dataloader_drop_last=d.get(C.DATALOADER_DROP_LAST, False),
            wall_clock_breakdown=d.get(C.WALL_CLOCK_BREAKDOWN, False),
            memory_breakdown=d.get(C.MEMORY_BREAKDOWN, False),
            dump_state=d.get(C.DUMP_STATE, False),
            fp16=_build(FP16Config, _sub(d, C.FP16)),
            bf16=_build(BF16Config, _sub(d, C.BF16)),
            zero_optimization=_build(ZeroConfig, _sub(d, C.ZERO_OPTIMIZATION)),
            optimizer=_build(OptimizerConfig, _sub(d, C.OPTIMIZER)),
            scheduler=_build(SchedulerConfig, _sub(d, C.SCHEDULER)),
            activation_checkpointing=_build(ActivationCheckpointingConfig, _sub(d, C.ACTIVATION_CHECKPOINTING)),
            flops_profiler=_build(FlopsProfilerConfig, _sub(d, C.FLOPS_PROFILER)),
            comms_logger=_build(CommsLoggerConfig, _sub(d, C.COMMS_LOGGER)),
            tensorboard=_build(MonitorBackendConfig, _sub(d, C.MONITOR_TENSORBOARD)),
            wandb=_build(MonitorBackendConfig, _sub(d, C.MONITOR_WANDB)),
            csv_monitor=_build(MonitorBackendConfig, _sub(d, C.MONITOR_CSV)),
            telemetry=_build(TelemetryConfig, _sub(d, C.TELEMETRY)),
            serving=_build(ServingConfig, _sub(d, C.SERVING)),
            resilience=_build(ResilienceConfig, _sub(d, C.RESILIENCE)),
            curriculum_learning=_build(CurriculumConfig, _sub(d, C.CURRICULUM_LEARNING)),
            progressive_layer_drop=_build(ProgressiveLayerDropConfig, _sub(d, C.PROGRESSIVE_LAYER_DROP)),
            eigenvalue=_build(EigenvalueConfig, _sub(d, "eigenvalue")),
            aio=_build(AioConfig, _sub(d, C.AIO)),
            sparse_attention=(_build(SparseAttentionConfig, d[C.SPARSE_ATTENTION]) if d.get(C.SPARSE_ATTENTION) else None),
            mesh=_build(MeshAxesConfig, _sub(d, C.MESH)),
            checkpoint=_build(CheckpointConfig, _sub(d, C.CHECKPOINT)),
            elasticity=_build(ElasticityConfig, _sub(d, C.ELASTICITY)),
            debug=_build(DebugConfig, _sub(d, "debug")),
            raw=d,
        )
        cfg._triangulate_batch(world_size)
        cfg._validate()
        return cfg

    # ------------------------------------------------------------------
    def _triangulate_batch(self, world_size: int) -> None:
        """train = micro × gas × dp_world (reference runtime/config.py:846)."""
        train, micro, gas = (
            self.train_batch_size,
            self.train_micro_batch_size_per_gpu,
            self.gradient_accumulation_steps,
        )
        ws = max(world_size, 1)
        if train is not None and micro is not None and gas is not None:
            pass
        elif train is not None and micro is not None:
            gas = train // (micro * ws)
        elif train is not None and gas is not None:
            micro = train // (gas * ws)
        elif micro is not None and gas is not None:
            train = micro * gas * ws
        elif train is not None:
            gas = 1
            micro = train // ws
        elif micro is not None:
            train = micro * ws
            gas = 1
        else:
            raise DeepSpeedConfigError(
                "at least one of train_batch_size / train_micro_batch_size_per_gpu must be set"
            )
        self.train_batch_size, self.train_micro_batch_size_per_gpu, self.gradient_accumulation_steps = train, micro, gas
        if train != micro * gas * ws:
            raise DeepSpeedConfigError(
                f"batch sizes inconsistent: train_batch_size={train} != "
                f"micro({micro}) * gas({gas}) * world({ws})"
            )

    def _validate(self) -> None:
        if self.fp16.enabled and self.bf16.enabled:
            raise DeepSpeedConfigError("fp16 and bf16 cannot both be enabled")
        if self.zero_optimization.stage > 0 and not (self.fp16.enabled or self.bf16.enabled):
            # ZeRO with fp32 is allowed (reference warns); keep permissive.
            pass

    # Convenience accessors matching the reference engine's names.
    @property
    def zero_enabled(self) -> bool:
        return self.zero_optimization.stage > 0

    @property
    def compute_dtype(self):
        import jax.numpy as jnp

        if self.bf16.enabled:
            return jnp.bfloat16
        if self.fp16.enabled:
            return jnp.float16
        return jnp.float32
