"""p90 of admitted_time - arrival_time over the counted requests that were
admitted (``RequestResult``'s own times)."""
import numpy as np

NAME, UNIT, LAYER = "queue_wait_p90_ms", "ms", "serving scheduler"


def read(ctx):
    s = ctx["serve"]
    if not s:
        return None
    waits = [r["admitted"] - r["arrival"] for r in s["counted"]
             if r["admitted"] is not None]
    return 1e3 * float(np.percentile(waits, 90)) if waits else None
