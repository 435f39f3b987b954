"""The device's own time for one run of a prefill program: the trace's seconds
of the operations named ``jit_prefill/...`` over the ``.../prefill`` spans of
the traced window, every bucket of the window together: a mean over the
cell's own mix (``decode_device_ms_mean`` has the method)."""
from . import call_anatomy as A

NAME, UNIT, LAYER = "prefill_device_ms_mean", "ms", "serving device programs"


def read(ctx):
    return A.device_ms(ctx, "prefill")
