"""Median duration of ``.../decode/fetch/copy`` in the host window: the
``device_get`` of a decode step's host-bound outputs (tokens, the sentinel, a
routed model's expert load) once they are ready, one round trip an array."""
from . import call_anatomy as A
from . import span_ring as R

NAME, UNIT, LAYER = "decode_copy_ms_p50", "ms", "serving device programs"


def read(ctx):
    return R.median_ms(A.host_part(ctx, "decode", "copy"))
