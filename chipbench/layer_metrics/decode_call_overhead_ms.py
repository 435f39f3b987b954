"""What a decode call costs beyond the device's work: the mean duration of the
traced window's ``.../decode`` spans less ``decode_device_ms_mean`` (the same
spans, the same weights). Host conversions and uploads, the key split, the
enqueue, the runtime's launch latency, the copy back: the number ROADMAP.md's
S2(f) is judged on.

Also writes the ``call_anatomy`` note (``call_anatomy.note``): for ``decode``
and ``prefill``, in the traced window and in the host window, mean and p50 of
the call, of ``dispatch`` and its three parts, of ``fetch`` and its two, the
device's time a run, ``enqueue + wait - device``, ``h2d`` / ``d2h``, the number
of runs, and the trace's device seconds by program."""
from . import call_anatomy as A

NAME, UNIT, LAYER = "decode_call_overhead_ms", "ms", "serving device programs"


def read(ctx):
    if not ctx["serve"]:
        return None
    A.note(ctx)
    return A.overhead_ms(ctx, "decode")
