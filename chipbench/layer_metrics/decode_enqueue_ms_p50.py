"""Median duration of ``.../decode/dispatch/enqueue`` in the host window (the
window ``decode_dispatch_ms_p50`` uses): the call of the watched decode program
itself: the proxy's bookkeeping, pjit's argument path over the parameter tree,
the batched upload of the host operands, the enqueue."""
from . import call_anatomy as A
from . import span_ring as R

NAME, UNIT, LAYER = "decode_enqueue_ms_p50", "ms", "serving device programs"


def read(ctx):
    return R.median_ms(A.host_part(ctx, "decode", "enqueue"))
