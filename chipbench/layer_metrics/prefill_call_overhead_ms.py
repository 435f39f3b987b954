"""What a prefill call costs beyond the device's work: the mean duration of the
traced window's ``.../prefill`` spans less ``prefill_device_ms_mean``
(``decode_call_overhead_ms`` has the method and writes the ``call_anatomy``
note for both)."""
from . import call_anatomy as A

NAME, UNIT, LAYER = "prefill_call_overhead_ms", "ms", "serving device programs"


def read(ctx):
    return A.overhead_ms(ctx, "prefill")
