"""Median over the host window's ``.../decode`` spans of ``h2d + d2h``: the
separate host->device operands a decode call hands over (host arrays given to
the program, eager uploads made for it, the key split's program as one) and the
separate arrays it fetches, as ``SlotWorker`` counts them on the span. The
counter of S2(f)'s mechanism: packing operands or results lowers it."""
import numpy as np

from . import call_anatomy as A

NAME, UNIT, LAYER = "decode_host_transfers", "count", "serving device programs"


def read(ctx):
    counts = [call.attrs["h2d"] + call.attrs["d2h"] for call, _ in A.host_calls(ctx, "decode")
              if "h2d" in call.attrs and "d2h" in call.attrs]
    return float(np.median(counts)) if counts else None
