"""bench.py backend-preflight hardening (ROADMAP item 1, r04/r05 regression).

The contract: a dead TPU backend is a RETRIABLE condition (bounded-backoff
preflight via resilience/retry.py), and every emitted JSON row carries
``platform`` + a ``comparable`` verdict so a fallback-backend (CPU) row can
never silently flatline the BENCH trajectory again. Pure host tests — the
child runner is stubbed; nothing spawns a subprocess or touches jax."""

import importlib.util
import json
import os

import pytest


@pytest.fixture(scope="module")
def bench():
    path = os.path.join(os.path.dirname(__file__), "..", "bench.py")
    spec = importlib.util.spec_from_file_location("bench", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_stamp_row_platform_and_comparable(bench):
    # every row also carries the perf-xray keys: mfu null / roofline
    # "unrated:<platform>" / step_anatomy null unless the child computed
    # real ones
    assert bench._stamp_row({"platform": "tpu"}, "full") == {
        "platform": "tpu", "bench_stage": "full", "comparable": True,
        "mfu": None, "roofline": "unrated:tpu", "step_anatomy": None,
        "spec_acceptance_rate": None,
        "spec_tokens_per_sec_per_request_ratio": None}
    assert bench._stamp_row({"platform": "cpu"}, "cpu_fallback")["comparable"] is False
    # a row that never ran anywhere stamps platform "none", non-comparable
    row = bench._stamp_row({}, "none")
    assert row["platform"] == "none" and row["comparable"] is False
    assert row["mfu"] is None and row["roofline"] == "unrated:none"
    assert row["step_anatomy"] is None  # labeled null, never fabricated
    # child-computed values are never overwritten by the stamp
    rated = bench._stamp_row({"platform": "tpu", "mfu": 0.41,
                              "roofline": "compute-bound",
                              "step_anatomy": {"overlap_verdict": "overlapped"}},
                             "full")
    assert rated["mfu"] == 0.41 and rated["roofline"] == "compute-bound"
    assert rated["step_anatomy"]["overlap_verdict"] == "overlapped"


def test_preflight_retries_with_bounded_backoff(bench):
    """Every failed attempt is retried with the resilience/retry backoff:
    monotone growth, capped, deterministic (same seed -> same delays)."""
    sleeps, sleeps2 = [], []
    dead = lambda env, timeout: (None, "timeout")
    diag = {"preflight": None, "preflight_attempts": 0}
    up, errs = bench._preflight_probe(dead, 5, 10, diag, sleep=sleeps.append)
    assert not up and len(errs) == 5
    assert diag["preflight_attempts"] == 5
    assert len(sleeps) == 4
    assert sleeps == sorted(sleeps)  # exponential growth
    assert all(s <= 120 * 1.25 for s in sleeps)  # max_delay cap (+jitter)
    bench._preflight_probe(dead, 5, 10,
                           {"preflight": None, "preflight_attempts": 0},
                           sleep=sleeps2.append)
    assert sleeps == sleeps2  # deterministic jitter: CI-reproducible


def test_preflight_success_midway_stops_retrying(bench):
    n = [0]

    def flaky(env, timeout):
        n[0] += 1
        if n[0] < 3:
            return None, "timeout"
        return json.dumps({"metric": "preflight", "platform": "tpu",
                           "elapsed_s": 1.0}), None

    diag = {"preflight": None, "preflight_attempts": 0}
    up, errs = bench._preflight_probe(flaky, 6, 10, diag, sleep=lambda s: None)
    assert up and len(errs) == 2 and diag["preflight_attempts"] == 3
    assert diag["preflight"]["platform"] == "tpu"


def test_preflight_cpu_comeup_is_retried_like_a_timeout(bench):
    """A missing TPU can manifest as a SILENT cpu fallback (jax init falls
    through instead of raising) — the same retriable condition as a timeout:
    a later fresh child can find the TPU once the backend comes up."""
    n = [0]

    def late_backend(env, timeout):
        n[0] += 1
        platform = "cpu" if n[0] < 3 else "tpu"
        return json.dumps({"metric": "preflight", "platform": platform,
                           "elapsed_s": 1.0}), None

    diag = {"preflight": None, "preflight_attempts": 0}
    up, errs = bench._preflight_probe(late_backend, 5, 10, diag,
                                      sleep=lambda s: None)
    assert up and n[0] == 3 and errs == ["came up on cpu"] * 2
    # genuinely CPU-only box: every attempt retried, then a clean verdict
    n[0] = 10**9
    up, errs = bench._preflight_probe(
        late_backend, 3, 10, {"preflight": None, "preflight_attempts": 0},
        sleep=lambda s: None)
    assert up  # 10**9 >= 3 -> tpu; now the all-cpu case:
    always_cpu = lambda env, timeout: (json.dumps(
        {"metric": "preflight", "platform": "cpu", "elapsed_s": 1.0}), None)
    up, errs = bench._preflight_probe(
        always_cpu, 3, 10, {"preflight": None, "preflight_attempts": 0},
        sleep=lambda s: None)
    assert not up and errs == ["came up on cpu"] * 3


def test_forced_preflight_failure_emits_non_comparable_row(
        bench, monkeypatch, capsys):
    """Acceptance: a forced preflight failure produces a RETRIED,
    explicitly non-comparable cpu_fallback row with the diagnosis — never a
    silent CPU datapoint."""
    monkeypatch.setenv("DSTPU_BENCH_FORCE_PREFLIGHT_FAIL", "1")
    monkeypatch.setenv("DSTPU_BENCH_PREFLIGHT_ATTEMPTS", "3")

    def fake_child(extra_env, timeout):
        if extra_env.get("JAX_PLATFORMS") == "cpu":
            return json.dumps({"metric": "gpt2 tflops", "value": 1.0,
                               "platform": "cpu"}), None
        raise AssertionError(f"unexpected child stage: {extra_env}")

    monkeypatch.setattr(bench, "_run_child", fake_child)
    monkeypatch.setattr(bench.time, "sleep", lambda s: None)
    assert bench._parent() == 0
    out = capsys.readouterr().out.strip().splitlines()
    row = json.loads(out[-1])
    assert row["bench_stage"] == "cpu_fallback"
    assert row["platform"] == "cpu"
    assert row["comparable"] is False
    assert row["preflight_attempts"] == 3  # the backend WAS retried
    assert "preflight failed" in row["diagnosis"]


def _run_bench_argv(*argv):
    import subprocess
    import sys

    return subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(__file__), "..",
                                      "bench.py"), *argv],
        capture_output=True, text=True, timeout=60)


@pytest.mark.parametrize("argv", [
    ("--surge", "-3"),            # negative operand
    ("--surge", "abc"),           # non-numeric operand
    ("--surge", "4"),             # below the structural minimum
    ("--surge", "30", "--surge-seed", "xyz"),  # non-numeric seed
    ("--surge", "30", "--surge-seed"),         # dangling seed flag
])
def test_surge_argv_contract_exits_2_with_usage(argv):
    """``--surge`` follows the ``--chaos``/``--chaos-serving`` contract:
    malformed operands exit 2 with a usage line on stderr — never a
    traceback, never a started drill. (The check runs before any jax
    import, so the subprocess is cheap.)"""
    proc = _run_bench_argv(*argv)
    assert proc.returncode == 2, (argv, proc.stderr)
    assert "usage: bench.py --surge" in proc.stderr
    assert "Traceback" not in proc.stderr


@pytest.mark.parametrize("argv", [
    ("--gateway-chaos", "7"),                       # unexpected operand
    ("--gateway-chaos", "--gateway-seed", "xyz"),   # non-numeric seed
    ("--gateway-chaos", "--gateway-seed"),          # dangling seed flag
])
def test_gateway_chaos_argv_contract_exits_2_with_usage(argv):
    """``--gateway-chaos`` follows the ``--chaos``/``--chaos-serving``/
    ``--surge`` contract: malformed operands exit 2 with a usage line on
    stderr — never a traceback, never a started drill."""
    proc = _run_bench_argv(*argv)
    assert proc.returncode == 2, (argv, proc.stderr)
    assert "usage: bench.py --gateway-chaos" in proc.stderr
    assert "Traceback" not in proc.stderr


@pytest.mark.parametrize("argv", [
    ("--router-chaos", "7"),                      # unexpected operand
    ("--router-chaos", "--router-seed", "xyz"),   # non-numeric seed
    ("--router-chaos", "--router-seed"),          # dangling seed flag
])
def test_router_chaos_argv_contract_exits_2_with_usage(argv):
    """``--router-chaos`` follows the sibling-drill contract: malformed
    operands exit 2 with a usage line on stderr — never a traceback,
    never a started drill."""
    proc = _run_bench_argv(*argv)
    assert proc.returncode == 2, (argv, proc.stderr)
    assert "usage: bench.py --router-chaos" in proc.stderr
    assert "Traceback" not in proc.stderr


@pytest.mark.parametrize("argv", [
    ("--tenant-chaos", "7"),                      # unexpected operand
    ("--tenant-chaos", "--tenant-seed", "xyz"),   # non-numeric seed
    ("--tenant-chaos", "--tenant-seed"),          # dangling seed flag
])
def test_tenant_chaos_argv_contract_exits_2_with_usage(argv):
    """``--tenant-chaos`` follows the sibling-drill contract: malformed
    operands exit 2 with a usage line on stderr — never a traceback,
    never a started drill."""
    proc = _run_bench_argv(*argv)
    assert proc.returncode == 2, (argv, proc.stderr)
    assert "usage: bench.py --tenant-chaos" in proc.stderr
    assert "Traceback" not in proc.stderr


@pytest.mark.parametrize("argv", [
    ("--disagg", "7"),                      # unexpected operand
    ("--disagg", "--disagg-seed", "xyz"),   # non-numeric seed
    ("--disagg", "--disagg-seed"),          # dangling seed flag
])
def test_disagg_argv_contract_exits_2_with_usage(argv):
    """``--disagg`` follows the sibling-drill contract: malformed operands
    exit 2 with a usage line on stderr — never a traceback, never a
    started drill."""
    proc = _run_bench_argv(*argv)
    assert proc.returncode == 2, (argv, proc.stderr)
    assert "usage: bench.py --disagg" in proc.stderr
    assert "Traceback" not in proc.stderr


@pytest.mark.parametrize("argv", [
    ("--chaos-search", "0"),                          # n below floor
    ("--chaos-search", "xyz"),                        # non-numeric operand
    ("--chaos-search", "8", "--chaos-search-seed"),   # dangling seed flag
    ("--chaos-search", "--chaos-search-seed", "xyz"),  # non-numeric seed
])
def test_chaos_search_argv_contract_exits_2_with_usage(argv):
    """``--chaos-search`` follows the sibling-drill contract: malformed
    operands exit 2 with a usage line on stderr — never a traceback,
    never a started search."""
    proc = _run_bench_argv(*argv)
    assert proc.returncode == 2, (argv, proc.stderr)
    assert "usage: bench.py --chaos-search" in proc.stderr
    assert "Traceback" not in proc.stderr


@pytest.mark.parametrize("argv", [
    ("--chaos-replay",),                  # missing FILE operand
    ("--chaos-replay", "--chaos-search"),  # flag where FILE belongs
])
def test_chaos_replay_argv_contract_exits_2_with_usage(argv):
    """``--chaos-replay`` requires its FILE operand: missing or
    flag-shaped operands exit 2 with a usage line on stderr."""
    proc = _run_bench_argv(*argv)
    assert proc.returncode == 2, (argv, proc.stderr)
    assert "usage: bench.py --chaos-replay" in proc.stderr
    assert "Traceback" not in proc.stderr


def test_drill_rows_carry_the_stamp_contract(bench):
    """Every CPU-pinned drill row (incl. the --gateway-chaos row) carries
    the full ``_stamp_row`` provenance block — platform cpu, comparable
    False, and the labeled-null perf-xray keys (``step_anatomy: null``) —
    via the shared ``_drill_stamp`` helper, so trajectory tooling can
    never mistake a correctness soak for a perf datapoint."""
    stamp = bench._drill_stamp()
    assert stamp == {"platform": "cpu", "comparable": False, "mfu": None,
                     "roofline": "unrated:cpu", "step_anatomy": None,
                     "spec_acceptance_rate": None,
                     "spec_tokens_per_sec_per_request_ratio": None,
                     # tenant-isolation stamps: labeled nulls on every
                     # non-tenant drill row (--tenant-chaos fills them)
                     "tenant_victim_ttft_p99_ratio": None,
                     "tenant_victim_sheds": None,
                     "tenant_aggressor_429s": None}
    # the stamp agrees with what _stamp_row would enforce on a cpu row
    stamped = bench._stamp_row(dict(stamp), "drill")
    assert stamped["comparable"] is False
    assert stamped["roofline"] == "unrated:cpu"
    assert stamped["step_anatomy"] is None


def test_tpu_row_stays_comparable(bench, monkeypatch, capsys):
    monkeypatch.delenv("DSTPU_BENCH_FORCE_PREFLIGHT_FAIL", raising=False)
    monkeypatch.setenv("DSTPU_BENCH_PREFLIGHT_ATTEMPTS", "2")

    def fake_child(extra_env, timeout):
        if extra_env.get(bench._MODE_ENV) == "preflight":
            return json.dumps({"metric": "preflight", "platform": "tpu",
                               "elapsed_s": 2.0, "n_chips": 4}), None
        if extra_env.get(bench._MODE_ENV) == "full":
            return json.dumps({"metric": "gpt2 tflops", "value": 90.0,
                               "platform": "tpu"}), None
        raise AssertionError(f"unexpected child stage: {extra_env}")

    monkeypatch.setattr(bench, "_run_child", fake_child)
    assert bench._parent() == 0
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert row["bench_stage"] == "full"
    assert row["platform"] == "tpu" and row["comparable"] is True
    assert row["preflight_attempts"] == 1
