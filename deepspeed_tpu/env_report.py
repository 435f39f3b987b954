"""Environment / compatibility report.

Reference: ``deepspeed/env_report.py`` + ``bin/ds_report``: prints installed
op compatibility, torch/cuda versions, and nvcc info. TPU-native: reports
JAX/jaxlib versions, visible devices and their kinds, mesh axis defaults,
Pallas availability, and the optional native host ops (C++ aio / cpu_adam).
"""

from __future__ import annotations

import importlib
import shutil
import sys


GREEN_OK = "\033[92m[OKAY]\033[0m"
YELLOW_NO = "\033[93m[NO]\033[0m"


def _try_version(mod: str) -> str | None:
    try:
        m = importlib.import_module(mod)
        return getattr(m, "__version__", "unknown")
    except Exception:
        return None


def collect() -> dict:
    info: dict = {"python": sys.version.split()[0]}
    for mod in ("jax", "jaxlib", "flax", "optax", "orbax.checkpoint", "numpy", "transformers"):
        info[mod] = _try_version(mod)
    # Device probe in a daemon thread with a deadline: a wedged accelerator
    # (e.g. a chip another process holds) must yield a report line, not a
    # hung report tool.
    import threading

    probe: dict = {}

    def _probe():
        try:
            import jax

            devs = jax.devices()
            probe["devices"] = [f"{d.platform}:{d.device_kind}" for d in devs]
            probe["default_backend"] = jax.default_backend()
        except Exception as e:
            probe["devices"] = []
            probe["device_error"] = str(e)[:200]

    t = threading.Thread(target=_probe, daemon=True)
    t.start()
    t.join(timeout=20.0)
    if t.is_alive():
        info["devices"] = []
        info["device_error"] = "device probe timed out after 20s"
    else:
        info.update(probe)
    try:
        import jax.experimental.pallas  # noqa: F401

        info["pallas"] = True
    except Exception:
        info["pallas"] = False
    info["gxx"] = shutil.which("g++")
    try:
        from .ops.native import aio_available, cpu_adam_available

        info["native_aio"] = aio_available()
        info["native_cpu_adam"] = cpu_adam_available()
    except Exception:
        info["native_aio"] = info["native_cpu_adam"] = False
    return info


def main() -> int:
    info = collect()
    print("-" * 60)
    print("deepspeed_tpu environment report (reference: ds_report)")
    print("-" * 60)
    for k, v in info.items():
        status = GREEN_OK if v else YELLOW_NO
        print(f"{k:20s} {status}  {v}")
    print("-" * 60)
    try:
        from .ops.op_builder import report as op_report

        print(op_report())
    except Exception as e:  # a diagnostic tool must say when it can't diagnose
        print(f"ops section unavailable: {type(e).__name__}: {e}")
    print("-" * 60)
    return 0


if __name__ == "__main__":
    sys.exit(main())
