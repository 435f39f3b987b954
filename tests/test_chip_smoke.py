"""chip_smoke.py's contract off the chip, and the one compile-cache rule.

On the chip the script proves itself. Here: with no accelerator it fails and
names what it found; the explicit tiny rehearsal runs every phase, the
four-device one included, and still never says ``"ok": true``; and every
entry point takes its compile-cache directory from ``utils/jax_env.py``."""

import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

RUNS = {
    # name -> (argv, virtual CPU devices)
    "no_chip": ([], 1),
    "rehearse": (["--rehearse"], 1),
    "rehearse_chips4": (["--rehearse", "--chips", "4"], 4),
}


@pytest.fixture(scope="module")
def smoke_runs():
    """All three runs at once, each in its own process (the script owns its
    process's devices); the children inherit the tests' compile cache."""
    procs = {}
    for name, (argv, n_dev) in RUNS.items():
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "XLA_FLAGS": f"--xla_force_host_platform_device_count={n_dev}"}
        procs[name] = subprocess.Popen(
            [sys.executable, os.path.join(REPO, "chip_smoke.py"), *argv],
            cwd=REPO, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    out = {}
    for name, p in procs.items():
        try:
            stdout, stderr = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            p.kill()
            stdout, stderr = p.communicate()
        lines = [json.loads(ln) for ln in stdout.splitlines() if ln.startswith("{")]
        out[name] = (p.returncode, stdout, stderr, lines)
    return out


def test_no_chip_fails_and_names_the_platform(smoke_runs):
    rc, stdout, stderr, lines = smoke_runs["no_chip"]
    assert rc != 0, stdout + stderr[-2000:]
    assert '"ok": true' not in stdout
    device = next(ln for ln in lines if ln.get("phase") == "device")
    assert device["platform"] == "cpu" and device["status"] == "fail"
    # no later phase ran: nothing after the device line but the failure
    assert [ln["phase"] for ln in lines] == ["setup", "device", "failed"]


@pytest.mark.parametrize("name,phases", [
    ("rehearse", ["setup", "device", "kernels", "train", "serve", "done"]),
    ("rehearse_chips4", ["setup", "device", "fsdp", "done"]),
])
def test_rehearsal_runs_every_phase_and_never_says_ok(smoke_runs, name, phases):
    rc, stdout, stderr, lines = smoke_runs[name]
    assert rc == 0, stdout + stderr[-2000:]
    assert '"ok": true' not in stdout and all("ok" not in ln for ln in lines)
    assert [ln["phase"] for ln in lines[:-1]] == phases
    assert all(ln.get("status", "pass") == "pass" for ln in lines[:-1])
    assert lines[-1]["rehearsal"] == "passed"
    assert lines[-1]["device"]["count"] == RUNS[name][1]


def test_compile_cache_rule(monkeypatch, tmp_path):
    from deepspeed_tpu.utils import jax_env

    before = jax.config.jax_compilation_cache_dir
    try:
        # set: nothing is set in code
        monkeypatch.setenv(jax_env.CACHE_ENV, str(tmp_path))
        assert jax_env.use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        # unset: one fixed path inside the checkout, exported to children
        monkeypatch.delenv(jax_env.CACHE_ENV)
        first = jax_env.use_compile_cache()
        assert first == jax_env.REPO_CACHE_DIR == os.path.join(REPO, ".jax_cache")
        assert os.environ[jax_env.CACHE_ENV] == first
        assert jax.config.jax_compilation_cache_dir == first
        assert jax_env.use_compile_cache() == first  # never a pid or a time
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("path", [
    "chip_smoke.py", "drills.py", "tests/conftest.py",
    "deepspeed_tpu/autotuning/trial_runner.py",
    "deepspeed_tpu/launcher/serving_worker.py",
])
def test_entry_points_take_the_cache_dir_from_the_helper(path):
    with open(os.path.join(REPO, path)) as f:
        src = f.read()
    assert "use_compile_cache(" in src
    assert "compilation_cache_dir" not in src and "COMPILATION_CACHE_DIR" not in src


@pytest.mark.parametrize("held,child_env,raises", [
    (None, None, False),                         # a CPU process holds no chip
    ("tpu", None, True),                         # holds it: a device child would hang
    ("tpu", {"JAX_PLATFORMS": "tpu"}, True),
    ("tpu", {"JAX_PLATFORMS": "cpu"}, False),    # the child is pinned off the chip
])
def test_one_process_per_chip_guard(monkeypatch, held, child_env, raises):
    """Every place that starts device children (the trial scheduler,
    WorkerSupervisor, the elastic agent, probe_backend) asks this first: a
    parent that holds the chip fails loudly instead of spawning."""
    from deepspeed_tpu.utils import jax_env

    assert jax_env.holds_accelerator() is None  # this process runs on the CPU
    monkeypatch.setattr(jax_env, "holds_accelerator", lambda: held)
    if raises:
        with pytest.raises(RuntimeError, match="holds the chip"):
            jax_env.require_chip_free("test", child_env)
    else:
        jax_env.require_chip_free("test", child_env)
