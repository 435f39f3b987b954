"""p99 of the time from a request's due time to the start of the first
``step()`` whose clock had passed it: how late the load generator (the
harness's own loop, which is also the engine's driver) looked at an arrival.
A validity guard for the latency metrics, not a property of the system."""
import numpy as np

NAME, UNIT, LAYER = "gen_lateness_p99_ms", "ms", "benchmark load generator"


def read(ctx):
    s = ctx["serve"]
    if not s or s["loop"] != "open":
        return None
    late = [r["seen"] - r["arrival"] for r in s["counted"] if r["seen"] is not None]
    return 1e3 * float(np.percentile(late, 99)) if late else None
