"""p90, over the requests that arrived in the window, of first_token_time -
arrival_time (from when the request was due; the engine's own times). A
request that never got a first token is charged the time to the end of the
run, so a failure lengthens the tail and does not vanish from it. Open loop
only. No cell reports it yet (PERF.md, PR 23: at 8 slots one window holds a
quarter of the hundred arrivals a p90 wants)."""
import numpy as np

NAME, UNIT = "ttft_p90_ms", "ms"


def read(ctx):
    s = ctx["serve"]
    if not s or s["loop"] != "open" or not s["counted"]:
        return None
    waits = [(r["first_token"] if r["first_token"] is not None else s["t_end"]) - r["arrival"]
             for r in s["counted"]]
    return 1e3 * float(np.percentile(waits, 90))
