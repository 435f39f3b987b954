"""Unified telemetry subsystem (deepspeed_tpu/telemetry/).

Contracts under test:
  * log-bucketed histogram quantiles track numpy on known distributions
    (bucket base 2**0.25 bounds relative error at ~9%);
  * span nesting produces slash-joined paths in both the registry and the
    JSONL event schema;
  * the recompile watchdog records every compilation with its abstract
    signature and raises on the SECOND compile of a compile-stable path —
    including the serving engine's real decode program;
  * the MonitorMaster bridge delivers registry snapshots as (tag, value,
    step) events to the existing backends;
  * ServingEngine.telemetry_snapshot() is the one call that reports
    TTFT/TPOT/occupancy, the recompile table, compile counts, and the
    comms summary together.

Models stay tiny and reuse test_serving's exact TransformerConfig so the
compiled programs are already in tests/.xla_cache.
"""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.telemetry import (
    JsonlExporter,
    MetricsRegistry,
    MonitorBridge,
    RecompileError,
    RecompileWatchdog,
    SpanTracer,
    Telemetry,
    prometheus_text,
    tracing,
)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dist", ["lognormal", "uniform", "exponential"])
def test_histogram_quantiles_track_numpy(dist):
    rng = np.random.default_rng(0)
    xs = {
        "lognormal": rng.lognormal(-3.0, 1.0, 20000),
        "uniform": rng.uniform(1e-3, 2.0, 20000),
        "exponential": rng.exponential(0.05, 20000),
    }[dist]
    reg = MetricsRegistry()
    h = reg.histogram("t/x")
    for v in xs:
        h.observe(v)
    assert h.count == len(xs)
    np.testing.assert_allclose(h.sum, xs.sum(), rtol=1e-9)
    assert h.min == xs.min() and h.max == xs.max()
    for q in (0.5, 0.9, 0.99):
        est, ref = h.quantile(q), float(np.quantile(xs, q))
        # geometric buckets, base 2**0.25: estimate within half a bucket
        assert abs(est - ref) / ref < 0.12, (dist, q, est, ref)
    # estimates can never leave the observed range
    assert h.min <= h.quantile(0.0) <= h.quantile(1.0) <= h.max


def test_histogram_edge_cases():
    reg = MetricsRegistry()
    h = reg.histogram("t/edge")
    assert h.quantile(0.5) == 0.0  # empty
    h.observe(0.0)  # zero lands in the underflow bucket
    h.observe(-1.0)
    h.observe(5.0)
    assert h.count == 3 and h.min == -1.0 and h.max == 5.0
    assert h.quantile(0.0) == -1.0


def test_registry_snapshot_and_prometheus_and_type_guard():
    reg = MetricsRegistry()
    reg.counter("serving/admissions").inc(3)
    reg.gauge("serving/queue_depth").set(7)
    reg.histogram("serving/ttft_sec").observe(0.25)
    snap = reg.snapshot()
    assert snap["counters"]["serving/admissions"] == 3
    assert snap["gauges"]["serving/queue_depth"] == 7
    hs = snap["histograms"]["serving/ttft_sec"]
    assert hs["count"] == 1 and hs["p50"] == 0.25
    text = prometheus_text(reg)
    assert "dstpu_serving_admissions_total 3" in text
    assert 'dstpu_serving_ttft_sec{quantile="0.50"}' in text
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("serving/admissions")


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_span_nesting_registry_and_jsonl_schema(tmp_path):
    path = str(tmp_path / "spans.jsonl")
    sink = JsonlExporter(path)
    tr = SpanTracer(sink)
    before = time.perf_counter()
    with tr.span("serve"):
        with tr.span("step") as sp:
            sp.annotate(kind="decode")
        with tr.span("step"):
            pass
    after = time.perf_counter()
    sink.close()
    events = [json.loads(line) for line in open(path)]
    assert [e["path"] for e in events] == ["serve/step", "serve/step", "serve"]
    inner = events[0]
    assert inner["type"] == "span" and inner["name"] == "step"
    assert inner["depth"] == 1 and inner["kind"] == "decode"
    assert {"t", "start_s", "dur_s"} <= set(inner)
    assert events[2]["depth"] == 0
    # start_s is on time.perf_counter()'s clock (the harness's, RequestResult's
    # and set_epoch's), not relative to a tracer-private epoch; ids link the tree
    assert all(before - 1e-6 <= e["start_s"] <= e["start_s"] + e["dur_s"] <= after + 1e-6
               for e in events)
    assert events[2]["parent"] is None
    assert events[0]["parent"] == events[1]["parent"] == events[2]["id"]
    assert not hasattr(tr, "_epoch")
    # the ring holds the same spans under slash-joined paths; parent covers children
    ring = tracing.spans(before)
    steps = [sp for sp in ring if sp.path == "serve/step"]
    (serve,) = [sp for sp in ring if sp.path == "serve"]
    assert len(steps) == 2
    assert serve.dur_s >= sum(sp.dur_s for sp in steps)


def test_span_device_sync_mode_blocks_on_output():
    tr = SpanTracer(device_sync=True)
    with tr.span("jit") as sp:
        out = jax.jit(lambda x: x * 2)(jnp.ones((16,)))
        sp.set_sync(out)  # block_until_ready at span exit must not raise
    assert sp.dur_s > 0


# ---------------------------------------------------------------------------
# recompile watchdog
# ---------------------------------------------------------------------------

def test_watchdog_raises_on_second_compile_of_stable_path():
    wd = RecompileWatchdog(MetricsRegistry(), mode="raise")
    f = wd.watch(jax.jit(lambda x: x + 1), "stable_f", stable=True)
    f(jnp.ones((4,)))  # first compile: allowed
    f(jnp.ones((4,)))  # cache hit: no event
    assert [e["n_for_name"] for e in wd.events] == [1]
    assert "float32[4]" in wd.events[0]["signature"]
    with pytest.raises(RecompileError, match="refused before execution"):
        f(jnp.ones((8,)))  # shape-driven retrace: refused, never reaches XLA
    # a caller-side RETRY of the same drifted call is refused again (the
    # refusal must not admit the signature), without logging a new event
    with pytest.raises(RecompileError, match="already-refused"):
        f(jnp.ones((8,)))
    table = {r["name"]: r for r in wd.compile_table()}
    # refusals are NOT compilations: XLA compiled exactly once
    assert table["stable_f"]["compiles"] == 1
    assert table["stable_f"]["refusals"] == 2
    assert table["stable_f"]["signatures"] == ["(float32[4])"]
    refusal_evs = [e for e in wd.events if e["type"] == "refusal"]
    assert len(refusal_evs) == 1 and "float32[8]" in refusal_evs[0]["signature"]
    # the original program is untouched by refusals
    assert np.asarray(f(jnp.ones((4,)))).tolist() == [2.0] * 4


def test_watchdog_warn_mode_records_without_raising():
    reg = MetricsRegistry()
    wd = RecompileWatchdog(reg, mode="warn")
    f = wd.watch(jax.jit(lambda x: x * x), "unstable_f", stable=False)
    for n in (3, 5, 7):
        f(jnp.ones((n,)))
    assert reg.snapshot()["counters"]["compile/unstable_f"] == 3
    assert reg.snapshot()["histograms"]["compile/wall_s"]["count"] == 3
    g = wd.watch(jax.jit(lambda x: x - 1), "stable_g", stable=True)
    g(jnp.ones((2,)))
    g(jnp.ones((3,)))  # violation in warn mode: recorded, no raise
    assert {r["name"]: r["compiles"] for r in wd.compile_table()}["stable_g"] == 2
    with pytest.raises(ValueError, match="already watches"):
        wd.watch(jax.jit(lambda x: x), "stable_g")


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

class _CaptureMonitor:
    enabled = True

    def __init__(self):
        self.events = []

    def write_events(self, events):
        self.events.extend(events)


def test_monitor_bridge_delivers_registry_snapshot():
    reg = MetricsRegistry()
    reg.counter("serving/admissions").inc(4)
    reg.gauge("train/loss").set(2.5)
    for v in (0.1, 0.2, 0.4):
        reg.histogram("serving/ttft_sec").observe(v)
    mon = _CaptureMonitor()
    sent = MonitorBridge(mon, prefix="Telemetry").push(reg, step=7)
    assert sent == mon.events
    tags = {t: v for t, v, _ in mon.events}
    assert tags["Telemetry/serving/admissions"] == 4
    assert tags["Telemetry/train/loss"] == 2.5
    assert {"Telemetry/serving/ttft_sec/p50", "Telemetry/serving/ttft_sec/p90",
            "Telemetry/serving/ttft_sec/p99"} <= set(tags)
    assert all(s == 7 for _, _, s in mon.events)


def test_monitor_bridge_through_csv_backend(tmp_path):
    from deepspeed_tpu.monitor.monitor import MonitorMaster
    from deepspeed_tpu.runtime.config import DeepSpeedConfig

    cfg = DeepSpeedConfig.from_dict(
        {"train_batch_size": 8,
         "csv_monitor": {"enabled": True, "output_path": str(tmp_path), "job_name": "t"}},
        world_size=8)
    mon = MonitorMaster(cfg)
    reg = MetricsRegistry()
    reg.counter("train/steps").inc(5)
    MonitorBridge(mon).push(reg, step=3)
    MonitorBridge(mon).push(reg, step=4)
    csvs = list((tmp_path / "t").glob("*.csv"))
    assert len(csvs) == 1
    rows = open(csvs[0]).read().splitlines()
    assert rows[0].startswith("step,") and len(rows) == 3  # header + 2 batches
    mon.close()


def test_csv_monitor_keeps_handles_open_across_batches(tmp_path):
    """Satellite: CsvMonitor must not reopen the file per event — one handle
    per tag, opened at first use, flushed per write_events batch."""
    from deepspeed_tpu.monitor.monitor import CsvMonitor
    from deepspeed_tpu.runtime.config import MonitorBackendConfig

    mon = CsvMonitor(MonitorBackendConfig(
        enabled=True, output_path=str(tmp_path), job_name="j"))
    for step in range(20):
        mon.write_events([("Train/loss", 1.0 / (step + 1), step),
                          ("Train/lr", 1e-3, step)])
    assert len(mon.files) == 2  # one persistent handle per output file
    loss_file = str(tmp_path / "j" / "Train_loss.csv")
    first_handle = mon.files[loss_file][0]
    mon.write_events([("Train/loss", 0.0, 99)])
    assert mon.files[loss_file][0] is first_handle
    # two tags that mangle to the same filename share the handle (one
    # header, serialized rows — no interleaved buffers)
    mon.write_events([("Train_loss", -1.0, 100)])
    assert len(mon.files) == 2
    # flush-per-batch: rows visible without close
    loss_rows = open(loss_file).read().splitlines()
    assert len(loss_rows) == 1 + 22 and loss_rows[0] == "step,Train/loss"
    assert sum(r == "step,Train/loss" for r in loss_rows) == 1
    mon.close()
    assert mon.files == {}


# ---------------------------------------------------------------------------
# serving integration (reuses test_serving's compiled-program shapes)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def inf_engine():
    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.models.transformer import Model, TransformerConfig

    cfg = TransformerConfig(
        vocab_size=97, max_seq_len=128, num_layers=2, num_heads=4,
        hidden_size=32, dtype=jnp.float32, loss_chunk_size=0,
        decode_attn="xla", pos_emb="rotary",
    )
    return InferenceEngine(model=Model(cfg), config={"dtype": "fp32"})


def _requests(n, seed=0):
    from deepspeed_tpu.inference import Request

    rng = np.random.default_rng(seed)
    return [
        Request(uid=i, prompt=rng.integers(0, 97, size=5 + 2 * i).astype(np.int32),
                max_new_tokens=3 + i)
        for i in range(n)
    ]


def test_serving_telemetry_snapshot_and_report(tmp_path, inf_engine):
    """Acceptance: JSONL + registry snapshot with TTFT/TPOT percentiles,
    slot occupancy, and a recompile table showing exactly 1 decode compile
    across staggered ragged admissions."""
    from deepspeed_tpu.inference import ServingEngine

    path = str(tmp_path / "serve.jsonl")
    srv = ServingEngine(inf_engine, n_slots=2, max_seq_len=128,
                        config={"jsonl_path": path})
    for r in _requests(4):
        srv.submit(r)
    res = srv.drain()
    assert len(res) == 4
    snap = srv.telemetry_snapshot()
    srv.telemetry.close()

    hists = snap["metrics"]["histograms"]
    counters = snap["metrics"]["counters"]
    assert hists["serving/ttft_sec"]["count"] == 4
    assert hists["serving/tpot_sec"]["count"] == 4
    assert hists["serving/tpot_sec"]["p50"] > 0
    assert 0 < hists["serving/slot_occupancy"]["max"] <= 1.0
    # the one compiling decode call is excluded from the latency histogram
    # (it belongs to compile/wall_s, not to the step-latency tail)
    assert hists["serving/decode_step_sec"]["count"] == counters["serving/decode_steps"] - 1
    assert counters["serving/admissions"] == 4
    assert counters["serving/evictions"] == 4
    assert counters["serving/tokens_out"] == sum(len(r.tokens) for r in res.values())
    # per-bucket prefill counts: 4 ragged prompts over power-of-two buckets
    assert sum(v for k, v in counters.items()
               if k.startswith("serving/prefill_bucket[")) == 4

    # recompile table: decode compiled exactly once, flagged stable
    table = {r["name"]: r for r in snap["recompile_table"]}
    assert table["serving/decode"]["compiles"] == 1
    assert table["serving/decode"]["stable"] is True
    assert snap["compiles"]["decode"] == 1
    assert "comm" in snap  # comms summary rides the same snapshot

    # JSONL carries request + compile events and the snapshot; the report
    # CLI renders all three sections
    events = [json.loads(line) for line in open(path)]
    kinds = {e["type"] for e in events}
    assert {"request", "compile", "snapshot"} <= kinds
    reqs = [e for e in events if e["type"] == "request"]
    assert len(reqs) == 4 and all(e["ttft_s"] >= 0 for e in reqs)

    from deepspeed_tpu.telemetry.report import load_events, summarize

    text = summarize(load_events(path))
    assert "recompile table" in text and "serving/decode" in text
    assert "request latency" in text and "ttft" in text
    assert "last registry snapshot" in text


def test_serving_watchdog_raises_on_forced_decode_recompile(inf_engine):
    """Acceptance: a second decode compilation is detected and raised.
    Forced by feeding the compile-stable decode program an operand with a
    drifted dtype — exactly the class of silent production retrace the
    watchdog exists to catch."""
    from deepspeed_tpu.inference import ServingEngine

    srv = ServingEngine(inf_engine, n_slots=2, max_seq_len=128,
                        config={"watchdog_mode": "raise"})
    for r in _requests(2, seed=1):
        srv.submit(r)
    srv.drain()  # one decode compile: fine
    assert srv.compile_counts()["decode"] == 1
    # reach through the scheduler/worker boundary: the WORKER owns the
    # compiled decode program and device cache
    w = srv.worker
    w._rng, k = jax.random.split(w._rng)
    with pytest.raises(RecompileError, match="serving/decode"):
        w._decode(
            w.params, w._cache,
            jnp.asarray(srv._last_tok, jnp.int16),  # drifted operand dtype
            jnp.asarray(srv._pos), jnp.asarray(srv._active), k,
            jnp.asarray(srv._temp), jnp.asarray(srv._top_k),
            jnp.asarray(srv._top_p),
        )
    # the guard fired BEFORE execution: the donated slot cache survives and
    # the engine keeps serving (only the drifted call was refused)
    assert srv.compile_counts()["decode"] == 1
    (r3,) = _requests(1, seed=9)
    r3.uid = 99
    srv.submit(r3)
    out = srv.drain()
    assert len(out[99].tokens) == r3.max_new_tokens


def test_engine_train_telemetry(tmp_path):
    """The training engine feeds the same spine: step-time histogram,
    throughput counters, boundary gauges, a watched train-step compile, and
    span + compile events in the JSONL log."""
    import deepspeed_tpu
    from simple_model import base_config, random_tokens, tiny_transformer

    path = str(tmp_path / "train.jsonl")
    cfg = base_config()
    cfg["mesh"] = {"data": -1}
    cfg["steps_per_print"] = 1  # host boundary every step: gauges update
    cfg["telemetry"] = {"enabled": True, "jsonl_path": path, "watchdog": "warn"}
    engine, _, _, _ = deepspeed_tpu.initialize(model=tiny_transformer(), config=cfg)
    batch = random_tokens(16)
    for _ in range(3):
        engine.train_batch(batch)
    snap = engine.telemetry_snapshot()
    engine.telemetry.close()

    m = snap["metrics"]
    assert m["histograms"]["train/step_time_sec"]["count"] == 3
    assert m["counters"]["train/steps"] == 3
    assert m["counters"]["train/samples"] == 3 * 16
    assert m["counters"]["train/tokens"] == 3 * 16 * 33
    assert m["gauges"]["train/loss"] > 0
    assert m["gauges"]["train/lr"] > 0
    assert "train/grad_norm" in m["gauges"]
    table = {r["name"]: r for r in snap["recompile_table"]}
    # the watchdog surfaces a real jax behavior: step 1's state leaves are
    # uncommitted init outputs, step 2's are committed sharded step outputs,
    # so pjit retraces ONCE (cache-hit-fast) and then reaches steady state —
    # the contract is no growth after step 2, not exactly-one trace
    steady = table["train/train_step"]["compiles"]
    assert 1 <= steady <= 2
    assert table["train/train_step"]["stable"] is False
    assert "comm" in snap

    events = [json.loads(line) for line in open(path)]
    compile_evs = [e for e in events if e["type"] == "compile"
                   and e["name"] == "train/train_step"]
    assert len(compile_evs) == steady  # no compile on step 3
    spans = [e for e in events if e["type"] == "span"]
    assert sum(e["path"] == "train/train_batch" for e in spans) == 3


def test_serving_telemetry_shared_bundle(inf_engine):
    """Passing telemetry= shares one registry across engines (fleet-level
    aggregation), and Telemetry defaults keep engines isolated."""
    from deepspeed_tpu.inference import ServingEngine

    shared = Telemetry()
    a = ServingEngine(inf_engine, n_slots=1, max_seq_len=128, telemetry=shared)
    b = ServingEngine(inf_engine, n_slots=1, max_seq_len=128)
    assert a.telemetry is shared and b.telemetry is not shared
