"""Chaos conductor (resilience/chaos.py + resilience/invariants.py): the
fault-space search, shrinking, and journal fail-closed contracts.

Host-only except one real-engine smoke: every schedule here drives the
in-process ``_FakeEngine`` fleet, so the whole module compiles ZERO new
XLA programs; the single real-engine test reuses the session
``tiny_serving_engine`` shapes (n_slots=2, max_seq_len=128) and stays
warm. The contracts under test:

  * schedules are replayable artifacts: canonical JSON round-trips
    byte-identically and ``generate`` is a pure function of its seed;
  * a run's outcome digest is deterministic — same schedule, same bytes;
  * injected control-plane crashes and journal outages recover with every
    invariant green (crash-once / recover-clean);
  * the journal is FAIL-CLOSED: a failed append leaves the durable file
    authoritative (write-then-apply), poisons the instance with a typed
    ``JournalUnavailableError``, and the router converts that into typed
    ``journal_unavailable`` rejects (503 at the gateway) plus an incident;
  * the shrinker is deterministic (same seed + violation -> byte-identical
    minimal artifact across two searches) and SOUND (the minimum still
    trips the original oracle — seeded mutation proof);
  * ``bin/dstpu_chaos_coverage`` holds at 13/13 registered sites.
"""

import json
import os
import subprocess
import sys

import pytest

from deepspeed_tpu.inference.journal import RequestJournal, replay
from deepspeed_tpu.inference.serving import Request, RequestResult
from deepspeed_tpu.resilience import JournalUnavailableError
from deepspeed_tpu.resilience.chaos import (DEFAULT_WORKLOAD, FAKE_SITES,
                                            ChaosRunner, FaultEntry,
                                            FaultSchedule, derive_seed,
                                            replay_repro, search,
                                            shrink_schedule, write_repro)
from deepspeed_tpu.resilience.faults import FaultInjector
from deepspeed_tpu.resilience.invariants import Violation

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# small workload => fast schedules; still enough surface for every fake site
WL = dict(DEFAULT_WORKLOAD, n_requests=5, n_replicas=2, max_new_tokens=4)


# -- schedules as artifacts --------------------------------------------------


def test_schedule_json_roundtrip_is_byte_identical():
    s = FaultSchedule.generate(derive_seed(7, 3), WL)
    assert s.entries, "generated schedule must arm at least one fault"
    text = s.to_json()
    back = FaultSchedule.from_json(text)
    assert back.to_json() == text
    assert back.as_dict() == s.as_dict()


def test_generate_is_pure_function_of_seed():
    a = FaultSchedule.generate(derive_seed(0, 11), WL)
    b = FaultSchedule.generate(derive_seed(0, 11), WL)
    c = FaultSchedule.generate(derive_seed(0, 12), WL)
    assert a.to_json() == b.to_json()
    assert a.to_json() != c.to_json()  # neighbouring index forks the stream
    # every generated site is in the fake-fleet registry subset
    for i in range(16):
        s = FaultSchedule.generate(derive_seed(3, i), WL)
        assert s.sites() <= set(FAKE_SITES)


def test_to_injector_config_maps_sites_onto_typed_keys():
    s = FaultSchedule(entries=[
        FaultEntry("replica_dead", key=1, at=3),
        FaultEntry("io_error", key=0, at=2),
        FaultEntry("garbage_logits", key=4, at=5),
        FaultEntry("router_crash", key=0, at=6),
    ], workload=WL)
    cfg = s.to_injector_config()
    assert [1, 3] in cfg["replica_dead_at"]
    assert cfg["io_error_journal_appends"] == [2]
    assert cfg["garbage_logits_uids"] == [4]
    assert cfg["garbage_logits_phase"] == "decode"
    assert cfg["garbage_logits_decode_step"] == 5
    assert cfg["router_crash_at"] == [6]
    assert cfg["enabled"] is True
    # two garbage entries on DIFFERENT decode steps cannot lower onto the
    # single-step injector knob — a loud error, not a silently dropped fault
    bad = FaultSchedule(entries=[FaultEntry("garbage_logits", key=1, at=2),
                                 FaultEntry("garbage_logits", key=2, at=3)],
                        workload=WL)
    with pytest.raises(ValueError):
        bad.to_injector_config()


# -- runs and digests --------------------------------------------------------


def test_clean_run_is_green_and_digest_deterministic():
    runner = ChaosRunner()
    ref = runner.reference(WL)
    sched = FaultSchedule(entries=[], workload=WL)
    a = runner.run(sched, reference=ref)
    b = runner.run(sched, reference=ref)
    assert not a.violations
    assert sorted(a.results) == a.accepted == list(range(1, 6))
    assert a.digest == b.digest  # same schedule, same bytes


def test_faulted_runs_recover_green_across_seeds():
    runner = ChaosRunner()
    ref = runner.reference(WL)
    fired_sites = set()
    for i in range(8):
        sched = FaultSchedule.generate(derive_seed(0, i), WL)
        out = runner.run(sched, reference=ref)
        assert not out.violations, \
            f"schedule {i} tripped: {[str(v) for v in out.violations]}"
        fired_sites |= set(out.fired)
    assert fired_sites, "8 schedules must fire at least one fault"
    # coverage counters accumulated in the shared registry, fired==survived
    counters = runner.telemetry.registry.snapshot()["counters"]
    for site in fired_sites:
        assert counters[f"chaos/site/{site}/fired"] == \
            counters[f"chaos/site/{site}/survived"]


def test_router_crash_recovers_exactly_once():
    runner = ChaosRunner()
    ref = runner.reference(WL)
    sched = FaultSchedule(entries=[FaultEntry("router_crash", at=3)],
                          workload=WL)
    out = runner.run(sched, reference=ref)
    assert out.crashes == 1 and out.restarts == 1
    assert not out.violations
    assert out.fired["router_crash"] == 1


def test_journal_outage_fails_closed_then_recovers():
    """The full-disk drill: an io_error armed on the journal append clock
    poisons the journal mid-workload; accepts fail closed with typed
    rejects, the control plane restarts over the durable prefix, and every
    request still reaches exactly one terminal."""
    runner = ChaosRunner()
    ref = runner.reference(WL)
    sched = FaultSchedule(entries=[FaultEntry("io_error", at=3)],
                          workload=WL)
    out = runner.run(sched, reference=ref)
    assert out.fired["io_error"] == 1
    assert out.restarts >= 1 and out.crashes == 0
    assert not out.violations
    counters = runner.telemetry.registry.snapshot()["counters"]
    assert counters["router/journal/append_failures"] >= 1


# -- journal fail-closed unit contracts -------------------------------------


def _req(uid):
    import numpy as np
    return Request(uid=uid, prompt=np.arange(4, dtype=np.int32) + 1,
                   max_new_tokens=3)


def _res(uid):
    import numpy as np
    return RequestResult(uid=uid, tokens=np.arange(3, dtype=np.int32),
                         prompt_len=4, arrival_time=0.0, finish_time=1.0,
                         status="ok")


def test_journal_append_failure_is_fail_closed(tmp_path):
    jpath = str(tmp_path / "j.dsjr")
    inj = FaultInjector({"enabled": True, "io_error_journal_appends": [3]})
    j = RequestJournal(jpath, injector=inj)
    j.record_submit(_req(1))
    j.record_submit(_req(2))
    with pytest.raises(JournalUnavailableError):
        j.record_terminal(1, _res(1))  # append #3: the armed write
    assert j.unavailable
    # poisoned instance refuses FURTHER appends without touching the disk
    with pytest.raises(JournalUnavailableError):
        j.record_submit(_req(3))
    # write-then-apply: the failed terminal was never applied to the
    # mirror, so mirror == durable file
    assert 1 in j.state.requests and 1 not in j.state.terminals
    state = replay(jpath)
    assert set(state.requests) == {1, 2} and not state.terminals


def test_journal_restart_over_durable_prefix_accepts_again(tmp_path):
    jpath = str(tmp_path / "j.dsjr")
    inj = FaultInjector({"enabled": True, "io_error_journal_appends": [2]})
    j = RequestJournal(jpath, injector=inj)
    j.record_submit(_req(1))
    with pytest.raises(JournalUnavailableError):
        j.record_submit(_req(2))  # fails closed; uid 2 never durable
    # the restart: a fresh journal over the same path, injector gone
    j2 = RequestJournal(jpath)
    assert set(j2.state.requests) == {1}
    j2.record_submit(_req(2))
    j2.record_terminal(1, _res(1))
    j2.close()
    state = replay(jpath)
    assert set(state.requests) == {2} and set(state.terminals) == {1}


def test_gateway_maps_journal_unavailable_to_503():
    from deepspeed_tpu.launcher.http_gateway import _REASON_STATUS
    assert _REASON_STATUS["journal_unavailable"] == 503


# -- shrinking: determinism and soundness ------------------------------------


def _garbage_tripwire(out):
    """Synthetic oracle: treat ANY garbage_logits firing as a violation —
    a stand-in for a real invariant regression that lets the shrinker be
    exercised while the production invariants stay green."""
    if out.fired.get("garbage_logits"):
        return [Violation("garbage_tripwire",
                          f"garbage fired {out.fired['garbage_logits']}x")]
    return []


def _search_artifacts(tmp_path, tag):
    art = str(tmp_path / tag)
    runner = ChaosRunner()
    summary = search(runner, 8, 0, workload=WL, artifact_dir=art,
                     oracles=[_garbage_tripwire])
    assert summary["violations"], "tripwire oracle must trip in 8 schedules"
    return art, summary


def test_shrinker_is_deterministic_byte_identical(tmp_path):
    art_a, sum_a = _search_artifacts(tmp_path, "a")
    art_b, sum_b = _search_artifacts(tmp_path, "b")
    assert [v["schedule_index"] for v in sum_a["violations"]] == \
        [v["schedule_index"] for v in sum_b["violations"]]
    for va, vb in zip(sum_a["violations"], sum_b["violations"]):
        with open(va["repro"], "rb") as f:
            bytes_a = f.read()
        with open(vb["repro"], "rb") as f:
            bytes_b = f.read()
        assert bytes_a == bytes_b  # same seed + violation -> same artifact
        assert va["minimal_entries"] <= va["entries"]


def test_shrinker_never_minimizes_away_the_violation(tmp_path):
    """Seeded mutation proof of ddmin soundness: for every tripped
    schedule across 10 seeds, the minimized schedule must still trip the
    SAME oracle — and be minimal (dropping any single remaining entry
    loses the violation or is a no-op the shrinker would have taken)."""
    runner = ChaosRunner()
    ref = runner.reference(WL)
    tripped_any = 0
    for seed in range(10):
        sched = FaultSchedule.generate(derive_seed(seed, 0), WL)
        out = runner.run(sched, reference=ref, oracles=[_garbage_tripwire])
        if not out.violations:
            continue
        tripped_any += 1
        want = {v.invariant for v in out.violations}

        def still_fails(cand):
            got = runner.run(cand, reference=ref,
                             oracles=[_garbage_tripwire])
            return want <= {v.invariant for v in got.violations}

        mini = shrink_schedule(sched, still_fails)
        assert mini.entries, "shrinker emptied a tripping schedule"
        assert still_fails(mini), "minimum no longer trips the oracle"
        for i in range(len(mini.entries)):
            dropped = mini.subset(j for j in range(len(mini.entries))
                                  if j != i)
            assert not still_fails(dropped), \
                f"seed {seed}: entry {i} was removable — not minimal"
    assert tripped_any >= 2, "mutation corpus too small to prove anything"


def test_repro_replay_is_bit_identical(tmp_path):
    runner = ChaosRunner()
    ref = runner.reference(WL)
    sched = FaultSchedule.generate(derive_seed(1, 4), WL)
    out = runner.run(sched, reference=ref, oracles=[_garbage_tripwire])
    path = str(tmp_path / "repro.json")
    write_repro(path, sched, out, search_seed=1, index=4)
    with open(path) as f:
        repro = json.load(f)
    got = replay_repro(ChaosRunner(), repro, oracles=[_garbage_tripwire])
    assert got["digest_match"] and got["violations_match"]
    assert got["digest"] == out.digest


# -- coverage gate -----------------------------------------------------------


def test_chaos_coverage_gate_reports_full_registry():
    gate = os.path.join(REPO, "bin", "dstpu_chaos_coverage")
    proc = subprocess.run([sys.executable, gate, "--repo", REPO],
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    n = len(FaultInjector.SITES)
    assert f"{n}/{n} sites exercised" in proc.stdout


def test_chaos_coverage_gate_flags_unexercised_site(tmp_path):
    """The gate FAILS when a registered site loses its last exercising
    test: clone the registry into a scratch repo whose test corpus only
    mentions one site."""
    pkg = tmp_path / "deepspeed_tpu" / "resilience"
    pkg.mkdir(parents=True)
    src = os.path.join(REPO, "deepspeed_tpu", "resilience", "faults.py")
    with open(src) as f:
        (pkg / "faults.py").write_text(f.read())
    tests = tmp_path / "tests"
    tests.mkdir()
    (tests / "test_only_one.py").write_text("# exercises replica_dead\n")
    gate = os.path.join(REPO, "bin", "dstpu_chaos_coverage")
    proc = subprocess.run([sys.executable, gate, "--repo", str(tmp_path)],
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 1
    assert "UNEXERCISED" in proc.stderr
    assert "router_crash" in proc.stderr


# -- real-engine mode --------------------------------------------------------


def test_chaos_runner_real_engine_schedule_green(tiny_serving_engine):
    """One real-engine schedule on the session model (warm shapes only):
    a replica death mid-decode must recover with every invariant green.
    The injector lives in the ROUTER config, so the engine factory can
    ignore it — fault delivery is a control-plane concern."""
    from deepspeed_tpu.inference import ServingEngine

    def engines(wl, fi):
        return [ServingEngine(tiny_serving_engine, n_slots=2,
                              max_seq_len=128,
                              config={"replica_id": f"r{i}"})
                for i in range(int(wl["n_replicas"]))]

    runner = ChaosRunner(engines=engines)
    wl = dict(WL, n_requests=3, n_replicas=2, max_new_tokens=3)
    sched = FaultSchedule(entries=[FaultEntry("replica_dead", key=0, at=2)],
                          workload=wl)
    out = runner.run(sched)
    assert not out.violations, [str(v) for v in out.violations]
    assert out.fired["replica_dead"] == 1
    assert sorted(out.results) == [1, 2, 3]
    assert all(r.status == "ok" for r in out.results.values())


@pytest.mark.slow  # subprocess drills.py boot; the warm sibling is
# test_faulted_runs_recover_green_across_seeds, which runs the same search
# machinery in-process on the fake fleet every tier-1 pass
def test_chaos_search_soak_subprocess(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "drills.py"),
         "--chaos-search", "8", "--chaos-search-seed", "1"],
        capture_output=True, text=True, timeout=300, cwd=str(tmp_path),
        env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    row = json.loads(proc.stdout.strip().splitlines()[-1])
    assert row["schedules_run"] == 8
    assert row["violations"] == []
    assert row["sites_covered"]
