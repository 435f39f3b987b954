"""p99 over ALL gaps between consecutive output tokens of the requests that
arrived in the window. The first token carries the engine's own
``first_token_time``; every later one is stamped when the harness's loop first
sees it on the streaming surface (``live_progress()``) after a ``step()``: the
stall a stream sees when a long prompt is prefilled beside it. Open loop only.
No cell reports it yet (PERF.md, PR 23)."""
import numpy as np

NAME, UNIT = "itl_p99_ms", "ms"


def read(ctx):
    s = ctx["serve"]
    if not s or s["loop"] != "open":
        return None
    gaps = [np.diff(r["token_times"]) for r in s["counted"] if len(r["token_times"]) > 1]
    return 1e3 * float(np.percentile(np.concatenate(gaps), 99)) if gaps else None
