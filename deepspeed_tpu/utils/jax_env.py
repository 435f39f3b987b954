"""JAX process plumbing shared by the entry points: the compile-cache rule,
and the one-process-per-chip rule for everything that starts children."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# one fixed, git-ignored directory inside the checkout (the path is part of
# the cache key: a directory that moves never hits)
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def use_compile_cache(default_dir: str = REPO_CACHE_DIR) -> str:
    """The one compile-cache rule: where ``JAX_COMPILATION_CACHE_DIR`` is
    set, set nothing; otherwise use ``default_dir`` and export it so child
    processes inherit the same cache. Returns the directory in effect."""
    cache_dir = os.environ.get(CACHE_ENV)
    if cache_dir:
        return cache_dir
    os.environ[CACHE_ENV] = default_dir
    # jax is already imported (here and by the package), so the environment
    # variable alone is too late for this process
    jax.config.update("jax_compilation_cache_dir", default_dir)
    return default_dir


def holds_accelerator() -> str | None:
    """The accelerator platform THIS process has initialized (``"tpu"``), or
    None. A chip belongs to one process at a time: once a process's jax
    backend is up on it, a child that needs it fails or hangs."""
    # no public probe answers this without initializing the backend itself
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return None
    platform = jax.default_backend()
    return None if platform == "cpu" else platform


def require_chip_free(who: str, child_env=None) -> None:
    """Called before starting a child that may need the device: raise if this
    process already holds the chip (unless ``child_env`` pins the child to
    the CPU platform), instead of letting the child fail or hang at init."""
    if child_env is not None and child_env.get("JAX_PLATFORMS") == "cpu":
        return
    platform = holds_accelerator()
    if platform is not None:
        raise RuntimeError(
            f"{who}: this process has initialized the {platform!r} backend and "
            "holds the chip, so a child process that needs it would fail or "
            "hang. Start device children from a process that has not touched "
            "jax devices (probe_backend() discovers the backend without "
            "taking it), or pin the children with JAX_PLATFORMS=cpu.")


def probe_backend(timeout: float = 120.0) -> dict:
    """Discover the backend WITHOUT initializing it in this process.

    Runs ``jax.default_backend()`` in a subprocess that exits before this
    returns, so the caller never takes the accelerator — essential for
    launchers that will spawn per-trial subprocesses needing the device (a
    parent holding the TPU makes every child fail at backend init). Returns
    {'backend': str, 'n_devices': int} or {'error': str} on timeout/failure."""
    require_chip_free("probe_backend")
    code = (
        "import json, jax\n"
        "print(json.dumps({'backend': jax.default_backend(),"
        " 'n_devices': jax.device_count()}))\n"
    )
    try:
        proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                              text=True, timeout=timeout)
        for line in reversed(proc.stdout.splitlines()):
            if line.startswith("{"):
                return json.loads(line)
        return {"error": f"rc={proc.returncode}: {(proc.stderr or '')[-200:]}"}
    except subprocess.TimeoutExpired:
        return {"error": f"backend probe timed out after {timeout}s"}
