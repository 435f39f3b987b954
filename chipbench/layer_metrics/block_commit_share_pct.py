"""The share of the host window's (active slot, block step) pairs whose pass was a COMMIT
(the pass over a finished block's final tokens that leaves its final K/V and reveals
nothing): 100 / (T + 1) under a full schedule, 20 at T = 4. What fusing the commit with
the next block's first pass would remove."""
from . import block_calls as B

NAME, UNIT, LAYER = "block_commit_share_pct", "%", "serving scheduler"


def read(ctx):
    found = B.calls(ctx, "window")
    pairs = sum(c.attrs["slots_active"] for c in found)
    return 100.0 * sum(c.attrs["commits"] for c in found) / pairs if pairs else None
