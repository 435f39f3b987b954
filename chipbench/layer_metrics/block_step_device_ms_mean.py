"""The device's own time for one run of the block-step program: the trace's seconds of
the operations named ``jit_block_step/...`` (self times, clipped to the traced window) over
the ``.../block_step`` spans that began in that window (a call that compiled left out).
What ``decode_device_ms_mean`` is for a model whose step yields one token a row."""
from . import block_calls as B

NAME, UNIT, LAYER = "block_step_device_ms_mean", "ms", "serving device programs"


def read(ctx):
    return B.device_ms(ctx, B.calls(ctx))
