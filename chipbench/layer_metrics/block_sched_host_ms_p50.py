"""``sched_host_ms_p50`` for a model that generates by diffusion over blocks: the median
self time of ``serve/step`` in the window, a scheduler iteration's duration minus what the
worker calls inside it cover, the BLOCK STEP among them (``block_calls.WORKER_CALLS``; the
accepted reader knows ``prefill``, ``chunk``, ``decode`` and ``verify`` and would charge
the scheduler a block step's whole call). Steps in which a call compiled are left out.
Absent where the window holds no ``.../block_step`` span."""
import numpy as np

from . import block_calls as B
from . import span_ring as R

NAME, UNIT, LAYER = "block_sched_host_ms_p50", "ms", "serving scheduler"


def read(ctx):
    if not B.calls(ctx, "window"):
        return None
    spans = R.started_in(R.serve_window(ctx))
    by_id = {sp.id: sp for sp in spans}
    steps = {sp.id: sp.t1 - sp.t0 for sp in spans if R.is_a(sp, "serve/step")}
    for sp in spans:
        if not any(R.is_a(sp, k) for k in B.WORKER_CALLS):
            continue
        top = sp
        while top.parent in by_id:
            top = by_id[top.parent]
        if top.id in steps:
            if sp.attrs.get("compiled"):
                del steps[top.id]
            else:
                steps[top.id] -= sp.t1 - sp.t0
    return 1e3 * float(np.median(list(steps.values()))) if steps else None
