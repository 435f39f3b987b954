"""Seconds of Python tracing and lowering in set-up (``xla/trace`` +
``xla/lower``): paid by every process whatever the compile cache holds."""
from . import setup_spans as S

NAME, UNIT, LAYER = "setup_trace_lower_s", "s", "start-up"


def read(ctx):
    return S.seconds(S.xla(ctx, "trace", "lower"))
