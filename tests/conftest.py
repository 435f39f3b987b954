"""Test harness: 8 virtual CPU devices stand in for a TPU slice.

The reference simulates "multi-node" as multi-process single-node NCCL
(tests/unit/common.py:66 DistributedTest). The TPU-native analogue is simpler:
one process with N XLA host-platform devices, meshes built over them exactly
as on a pod (SURVEY.md §4 "portable lessons" (a))."""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
# XLA's CPU client sizes its one thread pool as max(NPROC or the core count,
# device count), and an in-process collective holds a pool thread until all
# eight devices have joined it. On a box with <= 8 cores, two independent
# collectives that the devices enter in different orders (ZeRO-3's all-gather
# and all-reduce do, under load) then hold every thread: a deadlock that XLA
# ends by aborting the process after 40 s. Measured on 8 cores beside a busy
# suite: test_offload_zero3_composes aborted in 2 of 12 runs without this and
# in 0 of 24 with it.
os.environ.setdefault("NPROC", "32")
# NOTE: cache loads emit benign E-level "machine feature" lines (same-machine
# AOT bookkeeping); pytest captures stderr per test, so they surface only on
# failures — deliberately not suppressed (TF_CPP_MIN_LOG_LEVEL=3 would also
# hide real XLA errors).

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_threefry_partitionable", True)

# Persistent XLA compilation cache: the suite is COMPILE-bound on a 1-core
# box (~40 min cold; the smoke tier alone is ~7 min), and the programs are
# identical run to run — the cache turns warm re-runs into load-and-execute.
# Keyed by HLO hash, so code changes invalidate exactly the affected tests.
# The directory follows the one compile-cache rule (utils/jax_env.py): the
# environment's where it names one, tests/.xla_cache otherwise.
# Opt out with DSTPU_TEST_NO_XLA_CACHE=1 (e.g. to measure true compile time).
if not os.environ.get("DSTPU_TEST_NO_XLA_CACHE"):
    from deepspeed_tpu.utils.jax_env import use_compile_cache  # noqa: E402

    use_compile_cache(os.path.join(os.path.dirname(__file__), ".xla_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# Every executable a process has loaded keeps a dozen or more memory mappings
# (its code and constants), and jit's caches keep every executable. One xdist
# worker runs several test FILES in one process (--dist loadfile):
# tests/test_serving.py leaves ~31,000 mappings, tests/test_k_exaone.py adds
# ~37,000, and at vm.max_map_count (65,530) the next mmap fails inside
# ``deserialize_executable``: "Fatal Python error: Segmentation fault" (or
# "Aborted") at whichever test loads the executable that crosses the line, the
# worker down and the run's exit code with it (PR 42: three whole runs in three,
# always that pair of files on one worker; either file alone passes). So a file
# that ends above a quarter of the limit drops the loaded executables before
# the next one starts; what the next file needs again it reloads from the
# persistent cache above. Between FILES only: a test that counts its engine's
# compiles (``compile_counts()``) shares that engine with its own file at most.
def _mapped_regions() -> int:
    try:
        with open("/proc/self/maps", "rb") as f:
            return sum(1 for _ in f)
    except OSError:  # no procfs: nothing to count, nothing to hit
        return 0


def _map_limit() -> int:
    try:
        with open("/proc/sys/vm/max_map_count") as f:
            return int(f.read())
    except (OSError, ValueError):
        return 65530  # the kernel's default


@pytest.fixture(autouse=True, scope="module")
def _drop_loaded_executables_between_files():
    yield
    if _mapped_regions() > _map_limit() // 4:
        import gc

        jax.clear_caches()
        gc.collect()


# ---------------------------------------------------------------------------
# Per-test duration ledger (bin/check_tier1_budget): the warm tier-1 suite
# runs ~810-940s of an 870s driver budget with ±15% host drift — every run
# records {nodeid, when, duration, outcome} lines to tests/durations.jsonl
# (overwritten per session; gitignored) so the budget checker can PROJECT
# the drift band instead of the suite discovering a timeout the hard way.
# ---------------------------------------------------------------------------

_durations: list[dict] = []


def pytest_runtest_logreport(report):
    # setup durations matter too: session fixtures compile models there
    if report.when in ("setup", "call") and report.duration:
        _durations.append({
            "nodeid": report.nodeid,
            "when": report.when,
            "duration": round(report.duration, 4),
            "outcome": report.outcome,
        })


def pytest_sessionfinish(session, exitstatus):
    if not _durations:
        return
    import json

    path = os.path.join(os.path.dirname(__file__), "durations.jsonl")
    try:
        with open(path, "w") as f:
            for d in _durations:
                f.write(json.dumps(d) + "\n")
    except OSError:
        return  # read-only checkout: the ledger is best-effort

    # Incident-bundle quiescence verdict (docs/observability.md "Flight
    # recorder & SLOs"): a clean run must write ZERO unexpected incident
    # bundles under the test workdirs. Tests that create bundles ON
    # PURPOSE (trigger-matrix tests) drop a `.expected-incidents` marker
    # file beside them to opt out. Runs on every session — staging an
    # incident costs one trigger call, so even a narrow run can leak one.
    try:
        base = str(session.config._tmp_path_factory.getbasetemp())
        leaked = []
        for dirpath, _dirnames, filenames in os.walk(base):
            if any(f.startswith("incident-") and f.endswith(".json")
                   for f in filenames):
                marked = False
                probe = dirpath
                while probe.startswith(base):
                    if os.path.exists(os.path.join(probe,
                                                   ".expected-incidents")):
                        marked = True
                        break
                    probe = os.path.dirname(probe)
                if not marked:
                    leaked.extend(os.path.join(dirpath, f)
                                  for f in filenames
                                  if f.startswith("incident-")
                                  and f.endswith(".json"))
        if leaked:
            print(f"\n-- incident bundles: {len(leaked)} UNEXPECTED under "
                  f"{base} (expected 0) — first: {leaked[0]} --")
        else:
            print("\n-- incident bundles: 0 unexpected (quiescent) --")
    except Exception as e:  # noqa: BLE001 — advisory only, never fails a run
        print(f"\n[conftest] incident-bundle verdict skipped: {e}")

    # Warn-only budget verdict on every FULL warm run: project the fresh
    # ledger against the limit of the run that was made, so drift is
    # visible at the end of each session instead of surfacing as a driver
    # timeout. The driver's run hands whole files to six xdist workers
    # under 1,470 s: what decides it is max(longest file, total / workers),
    # which the checker prints with the three longest files; a one-process
    # run is held, as before, to the old line's 830 s on its total. Narrow
    # runs (-k / single file) are skipped — the checker would refuse their
    # partial ledger anyway — and nothing here can fail the suite.
    if len({d["nodeid"] for d in _durations}) < 300:
        return
    import subprocess
    import sys

    checker = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                           "bin", "check_tier1_budget")
    workers = getattr(session.config.option, "numprocesses", None)
    workers = workers if isinstance(workers, int) and workers > 1 else 1
    try:
        proc = subprocess.run(
            [sys.executable, checker, "--durations", path, "--workers", str(workers),
             "--budget", "1470" if workers > 1 else "830"],
            capture_output=True, text=True, timeout=30)
        print("\n-- tier-1 budget check (bin/check_tier1_budget, warn-only) --")
        for stream in (proc.stdout, proc.stderr):
            if stream.strip():
                print(stream.strip())
    except Exception as e:  # noqa: BLE001 — advisory only, never fails a run
        print(f"\n[conftest] tier-1 budget check skipped: {e}")

    # One-line lint verdict next to the budget verdict: the clean gate in
    # test_lint.py already FAILS the suite on findings — this line exists
    # so a full-run log shows the invariant-checker state at a glance even
    # when someone runs with `-k 'not lint'`. Warn-only by construction.
    lint = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "bin", "dstpu_lint")
    try:
        proc = subprocess.run([sys.executable, lint], capture_output=True,
                              text=True, timeout=60)
        verdict = (proc.stdout.strip().splitlines() or ["no output"])[-1]
        print(f"-- {verdict} (bin/dstpu_lint, warn-only) --")
    except Exception as e:  # noqa: BLE001 — advisory only, never fails a run
        print(f"[conftest] dstpu-lint verdict skipped: {e}")

    # One-line audit verdict beside the lint one: tests/test_audit.py is
    # the failing gate; this line keeps the interprocedural-checker state
    # visible on runs that deselect it. Warn-only by construction.
    audit = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                         "bin", "dstpu_audit")
    try:
        proc = subprocess.run([sys.executable, audit], capture_output=True,
                              text=True, timeout=60)
        verdict = (proc.stdout.strip().splitlines() or ["no output"])[-1]
        print(f"-- {verdict} (bin/dstpu_audit, warn-only) --")
    except Exception as e:  # noqa: BLE001 — advisory only, never fails a run
        print(f"[conftest] dstpu-audit verdict skipped: {e}")

    # One-line fault-site coverage verdict beside the others: every
    # FaultInjector site must keep at least one exercising tier-1 test or
    # drill (docs/resilience.md "Chaos conductor"). The failing gate
    # is tests/test_chaos.py; this line keeps the registry/coverage state
    # visible on runs that deselect it. Warn-only by construction.
    cov = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                       "bin", "dstpu_chaos_coverage")
    try:
        proc = subprocess.run([sys.executable, cov], capture_output=True,
                              text=True, timeout=30)
        verdict = (proc.stdout.strip().splitlines() or ["no output"])[-1]
        print(f"-- {verdict} (bin/dstpu_chaos_coverage, warn-only) --")
    except Exception as e:  # noqa: BLE001 — advisory only, never fails a run
        print(f"[conftest] chaos-coverage verdict skipped: {e}")


@pytest.fixture(scope="session")
def tiny_serving_engine():
    """ONE tiny InferenceEngine shared by every serving-side test module
    (test_serving, test_prefix_cache, ...). The suite is compile-bound: a
    single model config means every ServingEngine built on top of it reuses
    the same XLA programs (decode/prefill/chunk shapes hash identically into
    tests/.xla_cache), so new serving tests cost execution time, not compile
    time. Keep this config EXACTLY in sync across tests — a drifted vocab or
    hidden size forks the whole cached program set."""
    import jax.numpy as jnp

    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.models.transformer import Model, TransformerConfig

    cfg = TransformerConfig(
        vocab_size=97, max_seq_len=128, num_layers=2, num_heads=4,
        hidden_size=32, dtype=jnp.float32, loss_chunk_size=0,
        decode_attn="xla", pos_emb="rotary",
    )
    return InferenceEngine(model=Model(cfg), config={"dtype": "fp32"})


@pytest.fixture
def mesh8():
    from deepspeed_tpu.comm.mesh import MeshConfig, build_mesh

    return build_mesh(MeshConfig(data=-1))


@pytest.fixture
def rng():
    return jax.random.PRNGKey(0)
