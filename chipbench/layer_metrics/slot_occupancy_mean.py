"""Mean, over the steps of the window that decoded, of the slots that
decoded in the step over ``n_slots``."""
import numpy as np

NAME, UNIT, LAYER = "slot_occupancy_mean", "%", "serving scheduler"


def read(ctx):
    s = ctx["serve"]
    if not s:
        return None
    lo, hi = s["window"]
    busy = [n for ts, _, n, _ in s["steps"] if lo <= ts < hi and n > 0]
    return 100.0 * float(np.mean(busy)) / s["n_slots"] if busy else None
