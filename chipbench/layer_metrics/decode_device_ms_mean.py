"""The device's own time for one run of the decode program: the trace's seconds
of the operations named ``jit_decode/...`` (self times, clipped to the traced
window) over the ``.../decode`` spans of that window (an edge call by the share
of it inside; a call that compiled left out). What PERF.md's section 5 counted
by hand from a GEMM whose time a run was known."""
from . import call_anatomy as A

NAME, UNIT, LAYER = "decode_device_ms_mean", "ms", "serving device programs"


def read(ctx):
    return A.device_ms(ctx, "decode")
