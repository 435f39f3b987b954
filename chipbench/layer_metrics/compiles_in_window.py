"""Compilations that ended inside the measured window, counted from jax's own
monitoring events (every backend compile of the process, the system's and the
harness's alike). Must be 0: every shape is warmed in set-up."""
NAME, UNIT, LAYER = "compiles_in_window", "count", "entry points"


def read(ctx):
    return ctx["n_compiles"]
