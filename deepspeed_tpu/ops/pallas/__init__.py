"""Pallas TPU kernels and the one rule for when they run interpreted."""

import jax


def interpret_default() -> bool:
    """Whether a kernel called without an explicit ``interpret`` runs in the
    Pallas interpreter: on the CPU platform only (tests, rehearsals). On
    ``tpu`` it compiles through Mosaic; any other platform has neither."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas TPU kernels need the 'tpu' platform (or 'cpu' for the "
        f"interpreter); the default backend is {backend!r}")
