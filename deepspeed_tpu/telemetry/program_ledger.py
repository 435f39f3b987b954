"""XLA program ledger: cost-model + HBM accounting for every compiled program.

A benchmark emits aggregate rates and the telemetry spine records
wall-time histograms — neither says *where* step time and HBM go, or how far
a program sits from the hardware roof. The reference ships this layer as its
flops profiler + wall-clock breakdown (deepspeed/profiling/flops_profiler/);
the TPU-native version is cheaper because every hot path here is already a
small, NAMED inventory of long-lived compiled programs (``train/train_step``;
``serving/decode``, ``prefill[b]``, ``chunk_prefill[w]``, ``prefix_fetch``/
``prefix_store``, ``fill_slot``) that the RecompileWatchdog wraps.

The ledger rides that wrap: when the watchdog detects a compilation it calls
``ProgramLedger.capture`` with the call's arguments. Capture is cheap and
host-side — it stores only ``jax.ShapeDtypeStruct`` specs (shape/dtype/
sharding metadata; safe even for donated operands, whose avals outlive the
buffers) plus the measured compile wall time. Resolution is lazy and
memoized: the first ``table()`` call re-lowers each program from its specs
and ``.compile()``s it, which jax serves from its in-memory executable cache
(and the persistent compilation cache on disk) — XLA's own
``cost_analysis()`` (flops, bytes accessed) and ``memory_analysis()``
(argument/output/temp HBM) come back for free, with ZERO new entries in the
jit cache (``_cache_size`` is untouched — the stable-program contracts and
compile-count tests hold unchanged).

Joining the static ledger with the registry's measured wall-time histograms
yields the derived metrics the ROADMAP's perf push needs:

  * achieved TFLOPS per program   = flops / wall_p50
  * MFU                           = achieved / per-platform peak (a TPU
                                    generation table + a CPU fallback entry
                                    that stays LABELED, never given a TPU
                                    peak — fallback rows can't lie)
  * roofline verdict              = compute-bound vs hbm-bound from
                                    arithmetic intensity (flops / bytes)
                                    against the platform's critical
                                    intensity, with headroom to the roof

``hbm_snapshot`` is the ledger's sibling: it attributes live device memory
to named pools (params, opt state, slot KV cache, prefix pool) next to the
runtime's bytes-in-use/limit watermarks, with a configurable warn threshold.

Peak-table provenance, the roofline method and the metric catalog live in
docs/observability.md ("Program ledger").
"""

from __future__ import annotations

from typing import Optional

from ..utils.logging import logger

# ---------------------------------------------------------------------------
# per-platform peaks (dense bf16 TFLOPS per chip, HBM GB/s per chip,
# aggregate one-way ICI GB/s per chip — the Gbps figures in the Google
# Cloud TPU system-architecture docs divided by 8; see docs/observability.md
# for the provenance table). A generation missing here degrades to the labeled
# "unrated" entry — rows stay attributable, never wrong.
# ---------------------------------------------------------------------------

PEAKS: dict[str, dict] = {
    "tpu_v2": {"label": "TPU v2", "peak_tflops": 45.0, "peak_hbm_gbps": 700.0,
               "peak_ici_gbps": 62.0},
    "tpu_v3": {"label": "TPU v3", "peak_tflops": 123.0, "peak_hbm_gbps": 900.0,
               "peak_ici_gbps": 82.0},
    "tpu_v4": {"label": "TPU v4", "peak_tflops": 275.0, "peak_hbm_gbps": 1228.0,
               "peak_ici_gbps": 300.0},
    "tpu_v5e": {"label": "TPU v5e", "peak_tflops": 197.0, "peak_hbm_gbps": 819.0,
                "peak_ici_gbps": 200.0},
    "tpu_v5p": {"label": "TPU v5p", "peak_tflops": 459.0, "peak_hbm_gbps": 2765.0,
                "peak_ici_gbps": 600.0},
    "tpu_v6e": {"label": "TPU v6e", "peak_tflops": 918.0, "peak_hbm_gbps": 1640.0,
                "peak_ici_gbps": 448.0},
    # CPU fallback: rows are LABELED but never rated against a TPU peak
    "cpu": {"label": "cpu (unrated)", "peak_tflops": None, "peak_hbm_gbps": None,
            "peak_ici_gbps": None},
    "unknown": {"label": "unrated", "peak_tflops": None, "peak_hbm_gbps": None,
                "peak_ici_gbps": None},
}

# device_kind substrings -> PEAKS key; a kind that matches none is unrated
_KIND_PATTERNS = (
    ("v6e", "tpu_v6e"), ("v6 lite", "tpu_v6e"),
    ("v5e", "tpu_v5e"), ("v5 lite", "tpu_v5e"), ("v5litepod", "tpu_v5e"),
    ("v5p", "tpu_v5p"),
    ("v4", "tpu_v4"), ("v3", "tpu_v3"), ("v2", "tpu_v2"),
)


def platform_peaks(device=None) -> dict:
    """Resolve the current (or given) device to its peak entry:
    ``{platform, device_kind, label, peak_tflops, peak_hbm_gbps}``. CPU and
    unknown TPU generations come back with None peaks and a label — callers
    must render "unrated", never substitute a wrong peak."""
    import jax

    if device is None:
        device = jax.devices()[0]
    platform = getattr(device, "platform", "unknown")
    kind = str(getattr(device, "device_kind", "") or "")
    if platform == "cpu":
        entry = PEAKS["cpu"]
    else:
        low = kind.lower()
        key = next((k for pat, k in _KIND_PATTERNS if pat in low), "unknown")
        entry = PEAKS[key]
    return {"platform": platform, "device_kind": kind, **entry}


# ---------------------------------------------------------------------------
# AOT cost capture (shared with profiling/flops_profiler)
# ---------------------------------------------------------------------------

def _arg_spec(leaf):
    """ShapeDtypeStruct twin of a call argument: shape/dtype/sharding
    metadata only — holds no device buffer (a donated operand's aval
    outlives its storage), and lowering from it reproduces the executed
    program so ``.compile()`` is an executable-cache hit.

    Sharding is carried only for COMMITTED arrays (device_put onto a mesh/
    device): an uncommitted operand's incidental default-device placement
    must stay unspecified, like execution treats it — pinning it would make
    AOT lowering reject the mix with mesh-sharded peers."""
    import jax

    if isinstance(leaf, jax.ShapeDtypeStruct):
        # already a spec (resolve() re-enters through aot_cost): pass it
        # through VERBATIM — rebuilding would strip the committed-operand
        # sharding captured at compile time, and an unsharded re-lowering
        # would both miss the executable cache and cost-model the wrong
        # program on sharded configs
        return leaf
    if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
        sharding = (getattr(leaf, "sharding", None)
                    if getattr(leaf, "_committed", False) else None)
        try:
            return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                        sharding=sharding)
        # dstpu: allow[broad-except] -- spec capture is observability-only: ShapeDtypeStruct rejects exotic shardings with version-specific types, and the unsharded struct is the documented degraded answer
        except Exception:  # exotic sharding the struct can't carry
            return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)
    return leaf  # python scalars etc. lower as they were called


def aot_cost(fn, args, kwargs=None, hlo: bool = False) -> dict:
    """Cost + memory analysis of ``fn`` lowered at ``args``' signature —
    ONE shared lower().compile() path for the ledger and the flops
    profiler. Returns {flops, bytes_accessed, optimal_seconds?,
    argument_bytes, output_bytes, temp_bytes, alias_bytes, ...} with absent
    fields omitted; {} when the function can't be lowered.
    ``hlo=True`` additionally includes ``hlo_text`` (the post-optimization
    HLO of the SAME compiled artifact — the collective ledger's input;
    callers pop it rather than carrying megabytes into snapshots)."""
    import jax

    lower = getattr(fn, "lower", None)
    if lower is None:
        return {}
    specs, kw_specs = jax.tree.map(_arg_spec, (tuple(args), kwargs or {}))
    compiled = lower(*specs, **kw_specs).compile()
    out: dict = {}
    ca = compiled.cost_analysis() or {}
    flops = float(ca.get("flops", 0.0))
    by = float(ca.get("bytes accessed", 0.0))
    if flops > 0:
        out["flops"] = flops
    if by > 0:
        out["bytes_accessed"] = by
    opt = float(ca.get("optimal_seconds", 0.0))
    if opt > 0:
        out["optimal_seconds"] = opt
    ma = compiled.memory_analysis()
    for f in ("argument", "output", "temp", "alias", "generated_code"):
        v = getattr(ma, f"{f}_size_in_bytes", None)
        if v is not None:
            out[f"{f}_bytes"] = int(v)
    if hlo:
        out["hlo_text"] = compiled.as_text() or ""
    return out


# ---------------------------------------------------------------------------
# the ledger
# ---------------------------------------------------------------------------

class ProgramLedger:
    """Static cost ledger over the watchdog's program inventory.

    ``capture`` runs on the compile-detection path (cheap: spec extraction
    only); ``table`` resolves pending entries (memoized lazy AOT analysis),
    joins them with the registry's wall-time histograms via ``bind``ed
    patterns, and computes MFU/roofline rows. A binding can nominate a
    gauge name — ``table`` then publishes that program's MFU and arithmetic
    intensity as registry gauges so ``telemetry_snapshot()`` carries them.
    """

    def __init__(self, registry=None, enabled: bool = True,
                 collectives: bool = True, ici_gbps: float = 0.0):
        from .collective_ledger import CollectiveLedger

        self.enabled = enabled
        self.registry = registry
        self.entries: dict[str, dict] = {}   # name -> resolved/static row
        self._pending: dict[str, tuple] = {}  # name -> (fn, specs, kw_specs)
        # (prefix, wall_hist, gauge_prefix) join rules, first match wins
        self._bindings: list[tuple[str, str, Optional[str]]] = []
        self._peaks: Optional[dict] = None
        # collective X-ray (telemetry/collective_ledger.py): HLO-parsed
        # per-collective summaries from the SAME lazily-resolved executables
        self.collectives = CollectiveLedger(enabled=enabled and collectives)
        # operator override for odd topologies / tests; 0 = use the peak
        # table's per-generation entry
        self._ici_gbps = float(ici_gbps) or None
        self._pipeline: Optional[dict] = None  # set by the pipeline engine
        self._notes: dict[str, dict] = {}  # name prefix -> a builder's facts (annotate)

    @property
    def platform(self) -> dict:
        if self._peaks is None:
            try:
                self._peaks = platform_peaks()
            # dstpu: allow[broad-except] -- platform probing must degrade to the 'unknown' peak row (unrated, never wrong) in jax-less/device-less processes, whatever the backend raises
            except Exception:  # no jax/devices in this process
                self._peaks = {"platform": "unknown", "device_kind": "",
                               **PEAKS["unknown"]}
        return self._peaks

    def set_platform(self, peaks: dict) -> None:
        """Override peak resolution (tests pin a synthetic platform so MFU
        math is checked against hand-computed fixtures)."""
        self._peaks = dict(peaks)

    def set_mesh_shape(self, mesh_shape: dict) -> None:
        """Teach the collective ledger the engine's mesh axis sizes (in mesh
        axis order) so HLO replica groups map back to axis NAMES."""
        self.collectives.set_mesh_shape(mesh_shape)

    def set_pipeline(self, num_stages: int, micro_batches: int,
                     schedule: str) -> None:
        """Pipeline-engine nomination: attach the clocked schedule's bubble
        accounting (ticks = M+S-1, bubble = S-1 of them) to the train-step
        anatomy rows."""
        from .collective_ledger import pipeline_bubble_fraction

        self._pipeline = {
            "num_stages": int(num_stages),
            "micro_batches": int(micro_batches),
            "schedule": schedule,
            "bubble_fraction": pipeline_bubble_fraction(
                num_stages, micro_batches),
        }

    def annotate(self, prefix: str, **facts) -> None:
        """Facts a builder knows about its programs (what the train step's
        checkpoints keep: ``remat_saved``), carried on the row of every program
        whose name starts with ``prefix``, captured already or later."""
        self._notes.setdefault(prefix, {}).update(facts)
        for name, row in self.entries.items():
            if name.startswith(prefix):
                row.update(facts)

    # -- capture (watchdog compile-detection path) -----------------------

    def capture(self, name: str, fn, args, kwargs, compile_s: float) -> None:
        """Record one compilation of watched path ``name``. Only the FIRST
        signature per name is kept for cost analysis (stable paths have
        exactly one; an unstable path's later shapes update compile totals
        but the ledger row describes the first program). Never raises —
        this sits on the dispatch hot path."""
        if not self.enabled:
            return
        try:
            row = self.entries.get(name)
            if row is None:
                import jax

                specs, kw_specs = jax.tree.map(
                    _arg_spec, (tuple(args), dict(kwargs or {})))
                self.entries[name] = {
                    "name": name,
                    "compiles": 1,
                    "compile_s": float(compile_s),
                    **{k: v for prefix, facts in self._notes.items()
                       if name.startswith(prefix) for k, v in facts.items()},
                }
                self._pending[name] = (fn, specs, kw_specs)
            else:
                row["compiles"] += 1
                row["compile_s"] += float(compile_s)
        # dstpu: allow[broad-except] -- ledger capture rides the compile-event path of a LIVE dispatch: any failure kind must be logged and dropped, or observability could fail the program it observes
        except Exception as e:  # noqa: BLE001 — never break the dispatch
            logger.debug(f"program ledger capture failed for {name!r}: {e}")

    def bind(self, prefix: str, wall_hist: str,
             gauge: Optional[str] = None) -> None:
        """Join rule: programs whose name starts with ``prefix`` read their
        measured wall time from registry histogram ``wall_hist``; when
        ``gauge`` is given, the first matching program's MFU / intensity
        are ALSO published as ``<gauge>/mfu`` and ``<gauge>/arith_intensity``
        gauges (the engine's headline-program nomination)."""
        self._bindings = [b for b in self._bindings if b[0] != prefix]
        self._bindings.append((prefix, wall_hist, gauge))

    def _binding(self, name: str):
        for prefix, wall_hist, gauge in self._bindings:
            if name.startswith(prefix):
                return wall_hist, gauge
        return None, None

    # -- resolution ------------------------------------------------------

    def resolve(self) -> None:
        """Run the memoized AOT analysis for every captured-but-unresolved
        program. A failure marks the row (``error``) and is never retried —
        unresolvable programs stay in the table with their compile stats."""
        for name in list(self._pending):
            fn, specs, kw_specs = self._pending.pop(name)
            row = self.entries[name]
            try:
                cost = aot_cost(fn, specs, kw_specs,
                                hlo=self.collectives.enabled)
            # dstpu: allow[broad-except] -- lazy AOT cost resolution calls backend introspection that raises version/backend-specific types; the row records the error string and the snapshot stays serveable
            except Exception as e:  # noqa: BLE001 — introspection only
                row["error"] = f"{type(e).__name__}: {e}"
                logger.debug(f"program ledger resolve failed for {name!r}: {e}")
                continue
            # the HLO text feeds the collective X-ray and is NOT kept on the
            # row (megabytes per program; the summary is what snapshots carry)
            hlo_text = cost.pop("hlo_text", "")
            if hlo_text:
                try:
                    self.collectives.record(name, hlo_text)
                # dstpu: allow[broad-except] -- the collective parse is best-effort observability over backend-formatted text; a malformed module must degrade to "no collective view", never fail the snapshot
                except Exception as e:  # noqa: BLE001
                    logger.debug(
                        f"collective ledger parse failed for {name!r}: {e}")
            row.update(cost)
            flops = row.get("flops")
            by = row.get("bytes_accessed")
            if flops and by:
                row["arith_intensity"] = flops / by

    def _derive(self, row: dict, wall: Optional[dict]) -> dict:
        """Join one static row with its measured wall-time summary and the
        platform peaks -> achieved TFLOPS / MFU / roofline verdict."""
        peaks = self.platform
        out = dict(row)
        peak_tf = peaks.get("peak_tflops")
        peak_bw = peaks.get("peak_hbm_gbps")
        flops = out.get("flops")
        inten = out.get("arith_intensity")
        if wall and wall.get("count"):
            out["wall_p50_s"] = wall["p50"]
            out["wall_count"] = wall["count"]
            if flops and wall["p50"] > 0:
                out["achieved_tflops"] = flops / wall["p50"] / 1e12
        # roofline: static verdict from intensity vs the platform's critical
        # intensity; headroom relates achieved to the intensity-limited roof
        if peak_tf is None or peak_bw is None:
            out["roofline"] = "unrated:" + str(peaks.get("platform", "?"))
        elif inten is None:
            out["roofline"] = "unknown"
        else:
            critical = peak_tf * 1e12 / (peak_bw * 1e9)  # flops per byte
            bound = "compute-bound" if inten >= critical else "hbm-bound"
            roof_tf = min(peak_tf, inten * peak_bw / 1e3)  # GB/s*f/B -> TF
            out["roofline"] = bound
            out["roof_tflops"] = roof_tf
            ach = out.get("achieved_tflops")
            if ach:
                out["mfu"] = ach / peak_tf
                out["roof_fraction"] = ach / roof_tf if roof_tf else None
        return out

    def table(self, registry=None) -> list[dict]:
        """The resolved, derived ledger: one row per program with flops,
        bytes, intensity, compile stats, HBM footprint, measured wall time,
        achieved TFLOPS, MFU, and the roofline verdict — sorted by flops.
        Publishes bound gauges as a side effect (call BEFORE snapshotting
        the registry so the gauges land in the same snapshot)."""
        self.resolve()
        registry = registry if registry is not None else self.registry
        rows = []
        published: set[str] = set()  # gauge names already claimed this pass
        for name, row in self.entries.items():
            wall = None
            wall_hist, gauge = self._binding(name)
            if registry is not None and wall_hist is not None:
                h = registry.get(wall_hist)
                if h is not None and hasattr(h, "summary"):
                    wall = h.summary()
            derived = self._derive(row, wall)
            if (registry is not None and gauge is not None
                    and gauge not in published):
                # the FIRST captured program matching the binding owns the
                # headline gauge (deterministic: entries iterate in capture
                # order) — a fleet bundle's 'serving/decode#2' never
                # overwrites the nominated 'serving/decode' row's numbers
                if derived.get("mfu") is not None:
                    published.add(gauge)
                    registry.gauge(f"{gauge}/mfu").set(derived["mfu"])
                if derived.get("arith_intensity") is not None:
                    published.add(gauge)
                    registry.gauge(f"{gauge}/arith_intensity").set(
                        derived["arith_intensity"])
            rows.append(derived)
        return sorted(rows, key=lambda r: -(r.get("flops") or 0.0))

    def anatomy(self, registry=None) -> list[dict]:
        """Step-anatomy rows (telemetry/collective_ledger.step_anatomy): one
        per program, joining the cost model, the measured wall time, the
        platform peaks (incl. ICI) and the HLO collective summary into
        {compute_time_s, hbm_time_s, comm_time_by_axis,
        exposed_comm_estimate_s, overlap_verdict}. Publishes the nominated
        ``<gauge>/comm/*`` gauges as a side effect (call BEFORE snapshotting
        the registry). Unrated platforms keep static facts with labeled null
        times — never a fabricated comm roofline."""
        from .collective_ledger import step_anatomy

        self.resolve()
        registry = registry if registry is not None else self.registry
        peaks = self.platform
        rows = []
        published: set[str] = set()
        for name, row in self.entries.items():
            wall = None
            wall_hist, gauge = self._binding(name)
            if registry is not None and wall_hist is not None:
                h = registry.get(wall_hist)
                if h is not None and hasattr(h, "summary"):
                    wall = h.summary()
            arow = step_anatomy(row, wall, peaks,
                                self.collectives.get(name),
                                ici_gbps=self._ici_gbps)
            if self._pipeline is not None and name.startswith("train/"):
                arow["pipeline"] = dict(self._pipeline)
            if (registry is not None and gauge is not None
                    and gauge not in published):
                # same first-captured-program-owns-the-gauge rule as table()
                published.add(gauge)
                if arow.get("comm_time_s") is not None:
                    registry.gauge(f"{gauge}/comm/time_s").set(
                        arow["comm_time_s"])
                if arow.get("exposed_comm_estimate_s") is not None:
                    registry.gauge(f"{gauge}/comm/exposed_s").set(
                        arow["exposed_comm_estimate_s"])
                if arow.get("comm_bytes_total"):
                    registry.gauge(f"{gauge}/comm/bytes").set(
                        arow["comm_bytes_total"])
            rows.append(arow)
        return rows


# ---------------------------------------------------------------------------
# HBM memory ledger
# ---------------------------------------------------------------------------

def tree_bytes(tree) -> int:
    """Total buffer bytes of a pytree (metadata walk, no device sync)."""
    import jax
    import numpy as np

    total = 0
    for leaf in jax.tree.leaves(tree):
        nb = getattr(leaf, "nbytes", None)
        if nb is not None:
            total += int(nb)
        elif hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            total += int(np.prod(leaf.shape, initial=1)
                         * np.dtype(leaf.dtype).itemsize)
    return int(total)


def hbm_snapshot(pools: dict[str, int], warn_fraction: float = 0.9) -> dict:
    """Attribute device memory to named pools next to the runtime's own
    watermarks. ``pools`` maps pool name -> bytes (callers compute them with
    ``tree_bytes`` over the live state); the runtime side (bytes in use /
    peak / limit) comes from ``device.memory_stats()`` where the backend
    provides it. ``warn`` trips when bytes_in_use exceeds ``warn_fraction``
    of the limit — the report CLI flags the row."""
    from ..utils.memory import device_memory_stats

    pools = {k: int(v) for k, v in pools.items() if v}
    out: dict = {
        "pools": pools,
        "pool_total_bytes": sum(pools.values()),
        "warn_fraction": float(warn_fraction),
        "warn": False,
    }
    stats = device_memory_stats()
    if stats:
        in_use = int(stats.get("bytes_in_use", 0))
        limit = int(stats.get("bytes_limit", 0))
        out["device"] = {
            "bytes_in_use": in_use,
            "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", 0)),
            "bytes_limit": limit,
        }
        if limit > 0 and in_use > warn_fraction * limit:
            out["warn"] = True
    return out


__all__ = ["ProgramLedger", "aot_cost", "platform_peaks", "PEAKS",
           "tree_bytes", "hbm_snapshot"]
