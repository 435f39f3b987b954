"""Memory usage reporting (``see_memory_usage`` analogue).

The reference prints CUDA allocator stats at phase boundaries
(utils/__init__.py ``see_memory_usage``, called at runtime/engine.py:1606/
:1757/:1954). On TPU the equivalents are per-device ``memory_stats()``
(bytes_in_use / peak_bytes_in_use from the TPU runtime) plus host RSS from
/proc — there is no allocator cache to flush because XLA plans buffers at
compile time.
"""

from __future__ import annotations

from .logging import logger


def _host_rss_gb() -> float:
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) / 1024**2
    except OSError:
        pass
    return 0.0


def device_memory_stats(device=None) -> dict:
    """Per-device memory stats (empty dict when the backend lacks them)."""
    import jax

    device = device or jax.devices()[0]
    stats = getattr(device, "memory_stats", lambda: None)()
    return stats or {}


def mesh_memory_limit(mesh):
    """``bytes_limit`` of one device of ``mesh``, THE SAME NUMBER ON EVERY
    PROCESS of a multi-process run, or None where there is none to read. For
    whoever chooses a program by it (``runtime/engine._saving_what_fits``): hosts
    that traced different programs would hang in their collectives.

    Read from the first device of the mesh that THIS process is attached to
    (another process's device has no ``memory_stats()`` here); every process
    reads its own, and the numbers agree because the mesh holds one kind of
    device, which is checked: a mesh of mixed kinds gives None on every process.
    None too on a platform without memory figures (the CPU) and for a mesh that
    is described and not attached (a compile for a chip this host has not got:
    the caller hands the limit in)."""
    import jax

    devices = list(mesh.devices.flat)
    attached = set(jax.local_devices())
    mine = [d for d in devices if d in attached]
    if not mine or len({d.device_kind for d in devices}) != 1:
        return None
    return device_memory_stats(mine[0]).get("bytes_limit")


def device_bytes_held(tree) -> int:
    """Bytes ONE device holds of ``tree``'s arrays (or of ``ShapeDtypeStruct``s
    that carry a sharding), from the shardings: exact. Leaves in pinned host
    memory are not the device's."""
    import math

    import jax

    return sum(
        math.prod(x.sharding.shard_shape(x.shape)) * x.dtype.itemsize
        for x in jax.tree.leaves(tree)
        if getattr(x, "sharding", None) is not None and x.sharding.memory_kind != "pinned_host")


def see_memory_usage(message: str, force: bool = False) -> dict:
    """Log current + peak device memory and host RSS; returns the numbers.

    Mirrors the reference's call sites: drop a one-liner at a phase boundary.
    As in the reference, nothing is logged (or measured) unless ``force`` —
    callers thread a config bit through it.
    """
    import jax

    if not force:
        return {}
    stats = device_memory_stats()
    used = stats.get("bytes_in_use", 0) / 1024**3
    peak = stats.get("peak_bytes_in_use", 0) / 1024**3
    limit = stats.get("bytes_limit", 0) / 1024**3
    rss = _host_rss_gb()
    if force or used or peak:
        logger.info(
            "%s | device mem: %.2f GB used, %.2f GB peak, %.2f GB limit | host RSS %.2f GB",
            message, used, peak, limit, rss,
        )
    else:
        logger.info("%s | host RSS %.2f GB (device stats unavailable: %s)",
                    message, rss, jax.default_backend())
    return {"used_gb": used, "peak_gb": peak, "limit_gb": limit, "host_rss_gb": rss}
