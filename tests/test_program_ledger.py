"""Perf X-ray: program ledger, MFU/roofline math, HBM ledger, request
tracing, report CLI, and the tier-1 budget checker.

Contracts under test:

  * ledger capture rides the watchdog's compile detection and NEVER adds an
    XLA program: ``compile_counts()`` and the watchdog compile table are
    IDENTICAL before and after ``telemetry_snapshot()`` resolves the ledger
    (AOT ``lower().compile()`` is introspection, not a new trace);
  * MFU/roofline derivation matches hand-computed fixtures, and CPU (or any
    unknown platform) rows stay LABELED ``unrated`` — never rated against a
    TPU peak;
  * the HBM ledger attributes exact pool bytes and trips its warn threshold
    from the runtime's limit;
  * request timelines order arrived -> admitted -> chunk k -> first_token ->
    terminal on one engine, and a Router failover trace carries BOTH replica
    ids across the dead->clean edge;
  * the Perfetto export is schema-sane Chrome-trace JSON;
  * the report CLI renders roofline/HBM/timeline tables and ``--json``
    round-trips them;
  * ``bin/check_tier1_budget`` projects the duration ledger against the
    budget with the right exit codes.

Speed: the serving workload reuses the session ``tiny_serving_engine`` and
the exact (n_slots, prompt, max_new, feature) combinations test_router /
test_prefix_cache already compiled — NO new XLA program shapes; ledger
resolution itself is served from the in-process executable cache.
"""

import importlib.util
import json
import os

import numpy as np
import pytest

from deepspeed_tpu.telemetry import MetricsRegistry, ProgramLedger
from deepspeed_tpu.telemetry.program_ledger import hbm_snapshot, platform_peaks
from deepspeed_tpu.telemetry.request_trace import (RequestTracer,
                                                   request_timeline,
                                                   to_perfetto)

# the session-standard feature config (tests/test_prefix_cache.py,
# test_router.py) — same pool/chunk shapes, same cached programs
FEATURES = {
    "prefix_cache": {"enabled": True, "n_slots": 4, "block": 8,
                     "max_prefix_len": 64},
    "chunked_prefill": {"enabled": True, "chunk_size": 16},
}


def _prompts(sizes, seed=0, vocab=97):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=s).astype(np.int32) for s in sizes]


@pytest.fixture(scope="module")
def served(tiny_serving_engine, tmp_path_factory):
    """ONE served workload shared by the module: engine + snapshot + the
    JSONL the report CLI reads. Watchdog raise-mode proves the ledger adds
    no program shapes while the workload runs."""
    from deepspeed_tpu.inference import Request, ServingEngine

    path = str(tmp_path_factory.mktemp("ledger") / "serve.jsonl")
    srv = ServingEngine(
        tiny_serving_engine,
        config={"n_slots": 2, "max_seq_len": 128, "watchdog_mode": "raise",
                "jsonl_path": path, **FEATURES})
    reqs = [Request(uid=i, prompt=p, max_new_tokens=8)
            for i, p in enumerate(_prompts([5, 11, 23]))]
    res = srv.serve(reqs)
    assert all(r.ok for r in res.values())
    counts_before = srv.compile_counts()
    table_before = {r["name"]: r["compiles"]
                    for r in srv.telemetry.watchdog.compile_table()}
    snap = srv.telemetry_snapshot()
    srv.telemetry.close()
    return {"srv": srv, "snap": snap, "jsonl": path,
            "counts_before": counts_before, "table_before": table_before}


# ---------------------------------------------------------------------------
# ledger capture on the live program inventories
# ---------------------------------------------------------------------------

def test_serving_ledger_capture_zero_new_programs(served):
    """Acceptance: per-program ledger entries (flops, bytes, compile_s, hbm)
    in telemetry_snapshot(), with compile counts BIT-IDENTICAL to the
    pre-snapshot inventory — AOT cost analysis never traces a new program."""
    srv, snap = served["srv"], served["snap"]
    # zero new XLA programs: the jit caches saw nothing from the ledger
    assert srv.compile_counts() == served["counts_before"]
    assert {r["name"]: r["compiles"]
            for r in srv.telemetry.watchdog.compile_table()} \
        == served["table_before"]
    assert srv.compile_counts()["decode"] == 1

    rows = {r["name"]: r for r in snap["program_ledger"]}
    # the chunked-prefill workload's whole inventory is present
    assert "serving/decode" in rows
    assert any(n.startswith("serving/chunk_prefill[") for n in rows)
    assert "serving/prefix_store" in rows
    for name, r in rows.items():
        assert r["compiles"] >= 1 and r["compile_s"] > 0, name
        assert r.get("error") is None, (name, r.get("error"))
        assert r["flops"] > 0, name
        assert r["bytes_accessed"] > 0, name
        assert r["arith_intensity"] == pytest.approx(
            r["flops"] / r["bytes_accessed"])
    # decode joined with its measured wall-time histogram
    dec = rows["serving/decode"]
    assert dec["wall_p50_s"] > 0 and dec["wall_count"] >= 1
    assert dec["achieved_tflops"] == pytest.approx(
        dec["flops"] / dec["wall_p50_s"] / 1e12)


def test_cpu_rows_stay_unrated(served):
    """A CPU run must never be rated against a TPU peak: platform labeled,
    roofline verdict 'unrated:cpu', no mfu, no mfu gauge."""
    snap = served["snap"]
    assert snap["platform"]["platform"] == "cpu"
    assert snap["platform"]["peak_tflops"] is None
    for r in snap["program_ledger"]:
        assert r["roofline"] == "unrated:cpu"
        assert "mfu" not in r
    assert "serving/mfu" not in snap["metrics"]["gauges"]


def test_serving_hbm_ledger_pools(served):
    """HBM ledger attributes exact bytes to params / slot KV / prefix pool."""
    srv, snap = served["srv"], served["snap"]
    hbm = snap["hbm"]
    pools = hbm["pools"]
    # slot cache: k+v, [L=2, n_slots=2, Smax=128, H=4, Dh=8] f32
    assert pools["slot_kv_cache"] == 2 * 2 * 2 * 128 * 4 * 8 * 4
    # prefix pool: k+v, [L=2, 4 slots, 64, 4, 8] f32
    assert pools["prefix_pool"] == 2 * 2 * 4 * 64 * 4 * 8 * 4
    assert pools["params"] > 0
    assert hbm["pool_total_bytes"] == sum(pools.values())
    assert hbm["warn_fraction"] == srv.ledger_cfg.hbm_warn_fraction


def test_training_engine_ledger_and_hbm(tmp_path):
    """The training engine's snapshot carries a resolved train_step ledger
    row (XLA flops for the full fwd+bwd+update program), the derived
    achieved-TFLOPS join, and state attributed to params/opt pools —
    compile counts untouched by resolution."""
    import deepspeed_tpu
    from simple_model import base_config, random_tokens, tiny_transformer

    cfg = base_config()
    cfg["mesh"] = {"data": -1}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=tiny_transformer(), config=cfg)
    batch = random_tokens(16)
    for _ in range(2):
        engine.train_batch(batch)
    compiles_before = [r["compiles"]
                       for r in engine.telemetry.watchdog.compile_table()]
    snap = engine.telemetry_snapshot()
    assert [r["compiles"]
            for r in engine.telemetry.watchdog.compile_table()] \
        == compiles_before
    rows = {r["name"]: r for r in snap["program_ledger"]}
    step = rows["train/train_step"]
    assert step.get("error") is None, step.get("error")
    assert step["flops"] > 0 and step["bytes_accessed"] > 0
    assert step["wall_p50_s"] > 0
    assert step["achieved_tflops"] > 0
    assert step["roofline"] == "unrated:cpu"  # labeled, never a TPU peak
    pools = snap["hbm"]["pools"]
    assert pools["params"] > 0 and pools["opt_state"] > 0
    # AdamW: two moments per param
    assert pools["opt_state"] == 2 * pools["params"]
    # collective X-ray on the real compiled train step: the dp grad
    # reduction is attributed to the 'data' axis from the HLO, the static
    # overlap verdict is present, and the unrated CPU platform carries
    # labeled null times — never a fabricated comm roofline
    arows = {r["name"]: r for r in snap["step_anatomy"]}
    anat = arows["train/train_step"]
    assert anat["comm_bytes_by_axis"].get("data", 0) > 0
    assert anat["overlap_verdict"] in ("serialized", "overlapped",
                                       "partial-overlap")
    assert anat["comm_time_by_axis"] is None  # cpu: unrated
    assert anat["exposed_comm_estimate_s"] is None
    assert anat["wall_p50_s"] > 0


# ---------------------------------------------------------------------------
# MFU / roofline math against hand-computed fixtures
# ---------------------------------------------------------------------------

def _fixture_ledger(flops, bytes_accessed, wall_s, peak_tf, peak_bw):
    reg = MetricsRegistry()
    reg.histogram("wall").observe(wall_s)
    led = ProgramLedger(reg)
    led.entries["prog"] = {
        "name": "prog", "compiles": 1, "compile_s": 0.1,
        "flops": flops, "bytes_accessed": bytes_accessed,
        "arith_intensity": flops / bytes_accessed,
    }
    led.bind("prog", wall_hist="wall", gauge="fix")
    led.set_platform({"platform": "tpu", "device_kind": "fixture",
                      "label": "fixture", "peak_tflops": peak_tf,
                      "peak_hbm_gbps": peak_bw})
    return led, reg


def test_mfu_hbm_bound_fixture():
    # intensity 2 FLOPs/B < critical 4 (= 4 TF / 1000 GB/s) -> hbm-bound,
    # roof = 2 TF; wall 1.0s over 2e12 flops -> achieved 2 TF, mfu 0.5
    led, reg = _fixture_ledger(flops=2e12, bytes_accessed=1e12, wall_s=1.0,
                               peak_tf=4.0, peak_bw=1000.0)
    (row,) = led.table(reg)
    assert row["roofline"] == "hbm-bound"
    assert row["achieved_tflops"] == pytest.approx(2.0)
    assert row["mfu"] == pytest.approx(0.5)
    assert row["roof_tflops"] == pytest.approx(2.0)
    assert row["roof_fraction"] == pytest.approx(1.0)
    # the nominated gauges were published into the registry
    assert reg.snapshot()["gauges"]["fix/mfu"] == pytest.approx(0.5)
    assert reg.snapshot()["gauges"]["fix/arith_intensity"] == pytest.approx(2.0)


def test_mfu_compute_bound_fixture():
    # intensity 8 >= critical 4 -> compute-bound, roof = peak 4 TF;
    # achieved 1 TF -> mfu 0.25, quarter of the roof
    led, reg = _fixture_ledger(flops=8e12, bytes_accessed=1e12, wall_s=8.0,
                               peak_tf=4.0, peak_bw=1000.0)
    (row,) = led.table(reg)
    assert row["roofline"] == "compute-bound"
    assert row["achieved_tflops"] == pytest.approx(1.0)
    assert row["mfu"] == pytest.approx(0.25)
    assert row["roof_tflops"] == pytest.approx(4.0)
    assert row["roof_fraction"] == pytest.approx(0.25)


def test_unrated_platform_never_gets_a_peak():
    led, reg = _fixture_ledger(flops=2e12, bytes_accessed=1e12, wall_s=1.0,
                               peak_tf=4.0, peak_bw=1000.0)
    led.set_platform({"platform": "cpu", "device_kind": "cpu",
                      "label": "cpu (unrated)", "peak_tflops": None,
                      "peak_hbm_gbps": None})
    (row,) = led.table(reg)
    assert row["roofline"] == "unrated:cpu"
    assert "mfu" not in row and "roof_tflops" not in row
    assert "fix/mfu" not in reg.snapshot()["gauges"]


def test_arg_spec_passes_existing_specs_through_verbatim():
    """resolve() re-enters aot_cost with already-built specs: rebuilding
    them would strip the committed-operand sharding captured at compile
    time (ShapeDtypeStruct has no _committed attr), silently re-lowering
    an UNSHARDED twin of the program — specs must pass through untouched."""
    import jax

    from deepspeed_tpu.parallel.sharding import kv_slot_cache_spec  # noqa: F401
    from deepspeed_tpu.telemetry.program_ledger import _arg_spec

    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("d",))
    s = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("d"))
    spec = jax.ShapeDtypeStruct((8, 4), np.float32, sharding=s)
    out = _arg_spec(spec)
    assert out is spec  # verbatim, sharding intact
    assert _arg_spec(3) == 3  # python scalars untouched too


def test_first_matching_program_owns_the_gauge():
    """A fleet bundle's 'prog#2' must not overwrite the nominated first
    program's headline gauge (last-write-wins would flip with iteration
    order)."""
    led, reg = _fixture_ledger(flops=2e12, bytes_accessed=1e12, wall_s=1.0,
                               peak_tf=4.0, peak_bw=1000.0)
    led.entries["prog#2"] = {
        "name": "prog#2", "compiles": 1, "compile_s": 0.1,
        "flops": 8e12, "bytes_accessed": 1e12, "arith_intensity": 8.0,
    }
    rows = {r["name"]: r for r in led.table(reg)}
    assert rows["prog#2"]["mfu"] is not None  # both rows fully derived
    # but the gauge belongs to the FIRST captured match
    assert reg.snapshot()["gauges"]["fix/mfu"] == pytest.approx(
        rows["prog"]["mfu"])
    assert reg.snapshot()["gauges"]["fix/arith_intensity"] == pytest.approx(2.0)


def test_platform_peak_table_resolution():
    """device_kind strings map to the right generation; a kind the table
    does not know (a bare "v5" included) is unrated, never a guessed peak."""

    class _Dev:
        def __init__(self, platform, kind):
            self.platform, self.device_kind = platform, kind

    assert platform_peaks(_Dev("tpu", "TPU v4"))["peak_tflops"] == 275.0
    assert platform_peaks(_Dev("tpu", "TPU v5 lite"))["peak_tflops"] == 197.0
    assert platform_peaks(_Dev("tpu", "TPU v5p"))["peak_tflops"] == 459.0
    assert platform_peaks(_Dev("tpu", "TPU v7x"))["peak_tflops"] is None
    assert platform_peaks(_Dev("tpu", "TPU v5"))["peak_tflops"] is None
    assert platform_peaks(_Dev("cpu", "cpu"))["label"] == "cpu (unrated)"


def test_hbm_snapshot_warn_threshold(monkeypatch):
    from deepspeed_tpu.utils import memory as mem

    monkeypatch.setattr(mem, "device_memory_stats", lambda device=None: {
        "bytes_in_use": 95, "peak_bytes_in_use": 97, "bytes_limit": 100})
    snap = hbm_snapshot({"params": 60, "kv": 35, "empty": 0},
                        warn_fraction=0.9)
    assert snap["pools"] == {"params": 60, "kv": 35}  # zero pools dropped
    assert snap["pool_total_bytes"] == 95
    assert snap["device"]["bytes_limit"] == 100
    assert snap["warn"] is True
    assert hbm_snapshot({"params": 60}, warn_fraction=0.99)["warn"] is False


# ---------------------------------------------------------------------------
# request lifecycle tracing
# ---------------------------------------------------------------------------

def test_request_timeline_ordering(served):
    """Every request's merged timeline is arrived <= admitted <= chunk k <=
    first_token <= terminal, with chunk ks strictly increasing."""
    snap = served["snap"]
    for uid in (0, 1, 2):
        tl = request_timeline(snap, uid=uid)
        names = [e["event"] for e in tl if e["event"] != "prefix_hit"]
        assert names[0] == "arrived" and names[-1] == "terminal"
        order = {"arrived": 0, "admitted": 1, "chunk": 2, "first_token": 3,
                 "terminal": 4}
        ranks = [order[n] for n in names]
        assert ranks == sorted(ranks), (uid, names)
        ts = [e["t"] for e in tl]
        assert ts == sorted(ts)
        chunks = [e for e in tl if e["event"] == "chunk"]
        assert chunks, uid  # chunked prefill ran
        assert [c["k"] for c in chunks] == list(range(len(chunks)))
        term = tl[-1]
        assert term["status"] == "ok" and term["n_tokens"] == 8


def test_tracer_ring_buffer_bounded():
    tr = RequestTracer(capacity=4, replica_id=7)
    for i in range(10):
        tr.record(uid=i, event="arrived", t=float(i))
    evs = tr.events()
    assert len(evs) == 4  # oldest evicted
    assert [e["uid"] for e in evs] == [6, 7, 8, 9]
    assert all(e["replica_id"] == 7 for e in evs)
    with pytest.raises(ValueError):
        RequestTracer(capacity=0)


def test_failover_trace_carries_both_replica_ids(tiny_serving_engine):
    """A replica_dead failover timeline shows the request on the dead
    replica, the router's failover edge with BOTH ids, and the replay on
    the clean replica — merged from router + replica snapshots."""
    from deepspeed_tpu.inference import Request, Router

    router = Router(tiny_serving_engine, config={
        "n_slots": 2, "max_seq_len": 128, "watchdog_mode": "raise",
        "router": {"replicas": 2, "health": {"timeout": 30.0}},
        "fault_injection": {"enabled": True, "seed": 0,
                            "replica_dead_at": [[0, 3]]},
        **FEATURES})
    reqs = [Request(uid=i, prompt=p, max_new_tokens=8)
            for i, p in enumerate(_prompts([5, 11, 23]))]
    res = router.serve(reqs)
    assert all(r.ok for r in res.values())
    snap = router.telemetry_snapshot()

    failovers = [e for e in snap["router"]["request_trace"]
                 if e["event"] == "failover"]
    assert failovers, "replica_dead at step 3 must have failed something over"
    for ev in failovers:
        assert ev["from_replica"] == 0 and ev["to_replica"] == 1

    uid = failovers[0]["uid"]
    tl = request_timeline(snap, uid=uid)
    rids = {e.get("replica_id") for e in tl}
    # both replicas AND the router appear in one merged timeline
    assert {0, 1, "router"} <= rids
    # the replay re-enters replica 1 AFTER the failover edge and terminates
    i_fail = next(i for i, e in enumerate(tl) if e["event"] == "failover")
    after = tl[i_fail + 1:]
    assert any(e.get("replica_id") == 1 and e["event"] == "admitted"
               for e in after)
    assert after[-1]["event"] == "terminal" and after[-1]["status"] == "ok"


def test_perfetto_schema_sanity(served):
    tl = request_timeline(served["snap"])
    doc = to_perfetto(tl)
    json.loads(json.dumps(doc))  # serializable round-trip
    evs = doc["traceEvents"]
    assert evs
    assert {e["ph"] for e in evs} <= {"X", "i"}
    for e in evs:
        assert isinstance(e["name"], str)
        assert e["ts"] >= 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        if e["ph"] == "X":
            assert e["dur"] >= 0
    # each served request got its queued/prefill/decode slices
    for uid in (0, 1, 2):
        slices = {e["name"] for e in evs if e["ph"] == "X" and e["tid"] == uid}
        assert slices == {"queued", "prefill", "decode"}


# ---------------------------------------------------------------------------
# report CLI
# ---------------------------------------------------------------------------

def test_report_renders_roofline_hbm_and_timeline(served, capsys):
    from deepspeed_tpu.telemetry import report

    assert report.main([served["jsonl"]]) == 0
    out = capsys.readouterr().out
    assert "program roofline" in out
    assert "serving/decode" in out
    assert "unrated:cpu" in out
    assert "hbm memory ledger" in out
    assert "slot_kv_cache=" in out

    assert report.main([served["jsonl"], "--request", "1"]) == 0
    out = capsys.readouterr().out
    assert "request 1 timeline" in out
    assert "first_token" in out and "terminal" in out


def test_serving_anatomy_in_snapshot_zero_new_programs(served):
    """Acceptance: step anatomy appears in the serving engine's
    telemetry_snapshot() with compile counts untouched (the `served`
    fixture already proved count equality across the snapshot that built
    these rows; re-assert on the live engine), and every row on this
    unrated CPU platform carries labeled nulls for the time fields while
    keeping the static HLO facts."""
    srv, snap = served["srv"], served["snap"]
    rows = {r["name"]: r for r in snap["step_anatomy"]}
    assert "serving/decode" in rows
    for name, r in rows.items():
        assert r["comm_time_by_axis"] is None, name  # cpu: unrated
        assert r["comm_time_s"] is None and not r["comm_rated"], name
        assert r["exposed_comm_estimate_s"] is None, name
        assert "overlap_verdict" in r and "comm_bytes_by_axis" in r, name
    # the snapshot that computed the anatomy added no XLA programs
    assert srv.compile_counts() == served["counts_before"]


def test_report_step_anatomy_section(served, capsys):
    from deepspeed_tpu.telemetry import report

    assert report.main([served["jsonl"], "--step-anatomy"]) == 0
    out = capsys.readouterr().out
    assert "step anatomy" in out
    assert "serving/decode" in out
    assert "overlap" in out


def test_report_json_roundtrip(served, capsys, tmp_path):
    from deepspeed_tpu.telemetry import report

    assert report.main([served["jsonl"], "--json", "--request", "2"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert set(doc) == {"snapshot", "roofline", "hbm", "step_anatomy",
                        "comm_reconcile", "requests", "request_timeline"}
    names = {r["name"] for r in doc["roofline"]}
    assert "serving/decode" in names
    # step-anatomy rows round-trip with the acceptance keys, labeled nulls
    # on this unrated CPU run
    arows = {r["name"]: r for r in doc["step_anatomy"]}
    assert "serving/decode" in arows
    dec = arows["serving/decode"]
    assert dec["comm_time_by_axis"] is None and dec["comm_rated"] is False
    assert dec["exposed_comm_estimate_s"] is None
    assert "overlap_verdict" in dec
    assert doc["hbm"][0]["pools"]["slot_kv_cache"] > 0
    assert {r["uid"] for r in doc["requests"]} == {0, 1, 2}
    assert doc["request_timeline"][0]["uid"] == 2

    pf_path = str(tmp_path / "trace.json")
    assert report.main([served["jsonl"], "--perfetto", pf_path]) == 0
    capsys.readouterr()
    pf = json.load(open(pf_path))
    assert pf["traceEvents"]


# ---------------------------------------------------------------------------
# timer unification (satellite)
# ---------------------------------------------------------------------------

def test_timer_mirrors_into_registry_and_deprecates_standalone(monkeypatch):
    from deepspeed_tpu.utils import timer as timer_mod

    reg = MetricsRegistry()
    timers = timer_mod.SynchronizedWallClockTimer(registry=reg)
    t = timers("fwd")
    t.start(); t.stop()
    t.start(); t.stop()
    h = reg.snapshot()["histograms"]["timer/fwd_sec"]
    assert h["count"] == 2 and h["p50"] >= 0

    warns = []
    monkeypatch.setattr(timer_mod.logger, "warning",
                        lambda *a, **k: warns.append(a))
    timer_mod._standalone_warned = False
    timer_mod.SynchronizedWallClockTimer()
    timer_mod.SynchronizedWallClockTimer()
    assert len(warns) == 1  # one-shot, not per instance
    assert "deprecated" in warns[0][0]


def test_flops_profiler_uses_shared_aot_path():
    """Satellite: the profiler's XLA cross-check comes from the same
    aot_cost capture the ledger uses — flops AND bytes in one dict."""
    import jax.numpy as jnp

    from deepspeed_tpu.profiling.flops_profiler.profiler import FlopsProfiler

    x = jnp.ones((32, 32), jnp.float32)
    res = FlopsProfiler().profile(lambda a: (a @ a).sum(), x, time_it=False)
    assert res.xla_cost.get("flops", 0) > 0
    assert res.xla_flops == res.xla_cost["flops"]
    assert res.xla_cost.get("bytes_accessed", 0) > 0
    assert res.total_flops > 0  # analytic walker still independent


# ---------------------------------------------------------------------------
# tier-1 budget checker (satellite)
# ---------------------------------------------------------------------------

def _load_budget_checker():
    from importlib.machinery import SourceFileLoader

    path = os.path.join(os.path.dirname(__file__), os.pardir, "bin",
                        "check_tier1_budget")
    loader = SourceFileLoader("check_tier1_budget", path)
    spec = importlib.util.spec_from_loader("check_tier1_budget", loader)
    mod = importlib.util.module_from_spec(spec)
    loader.exec_module(mod)
    return mod


def _write_durations(path, rows):
    with open(path, "w") as f:
        for nodeid, dur in rows:
            f.write(json.dumps({"nodeid": nodeid, "when": "call",
                                "duration": dur, "outcome": "passed"}) + "\n")


def test_check_tier1_budget_exit_codes(tmp_path, capsys):
    chk = _load_budget_checker()
    led = str(tmp_path / "durations.jsonl")

    # missing / empty ledger -> usage error
    assert chk.main(["--durations", led]) == 2
    _write_durations(led, [])
    assert chk.main(["--durations", led]) == 2

    # a PARTIAL ledger (narrow -k / single-file run overwrote the full
    # suite's) is refused, never projected as a healthy budget
    _write_durations(led, [("t::a", 1.0), ("t::b", 2.0)])
    assert chk.main(["--durations", led]) == 2
    assert "narrow pytest run" in capsys.readouterr().err

    # comfortably inside the budget (band included)
    _write_durations(led, [("t::a", 100.0), ("t::b", 200.0)])
    assert chk.main(["--durations", led, "--budget", "830",
                     "--min-tests", "0"]) == 0
    out = capsys.readouterr()
    assert "OK" in out.out and "300s measured" in out.out

    # inside, but the +drift edge crosses -> warn, still 0
    _write_durations(led, [("t::a", 800.0)])
    assert chk.main(["--durations", led, "--budget", "830",
                     "--drift", "0.15", "--min-tests", "0"]) == 0
    assert "WARNING" in capsys.readouterr().err

    # over budget -> flag (exit 1) and name the slowest test
    _write_durations(led, [("t::slowest", 700.0), ("t::b", 200.0)])
    assert chk.main(["--durations", led, "--budget", "830",
                     "--min-tests", "0"]) == 1
    out = capsys.readouterr()
    assert "FAIL" in out.err and "t::slowest" in out.out


@pytest.mark.parametrize("case,rows,code,says", [
    # six workers, whole files each: ONE long file decides the run, not the total / 6
    ("one_long_file", [("tests/a.py::x", 900.0), ("tests/a.py::y", 700.0),
                       ("tests/b.py::x", 200.0)], 1, "longest file 1600s"),
    # many even files: the total over the workers decides, and it fits
    ("even_files", [(f"tests/f{i}.py::x", 300.0) for i in range(12)], 0, "= 600s"),
    # inside, but one slow host away: the band is the deciding number's, not the total's
    ("drift_edge", [("tests/a.py::x", 1400.0), ("tests/b.py::x", 100.0)], 0, "= 1400s"),
])
def test_check_tier1_budget_holds_the_longest_file_under_workers(tmp_path, capsys, case, rows,
                                                                code, says):
    """``--workers``: what decides the driver's run is max(longest file, total / workers)."""
    chk = _load_budget_checker()
    led = str(tmp_path / "durations.jsonl")
    _write_durations(led, rows)
    assert chk.main(["--durations", led, "--budget", "1470", "--workers", "6",
                     "--min-tests", "0"]) == code
    out = capsys.readouterr()
    assert says in out.out and f"the longest files are {rows[0][0].split('::')[0]}" in out.out
    assert ("FAIL" in out.err) == (code == 1)
    assert ("WARNING" in out.err) == (case == "drift_edge")


def test_conftest_writes_durations_ledger():
    """The hook in THIS session has been recording: the previous suite run's
    ledger (if any) parses, and the in-memory buffer for the current run is
    accumulating entries."""
    import conftest

    assert any(d["nodeid"] for d in conftest._durations)
    assert all({"nodeid", "when", "duration", "outcome"} <= set(d)
               for d in conftest._durations)
