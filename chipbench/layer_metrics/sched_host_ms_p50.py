"""Median self time of ``serve/step`` in the window: a scheduler iteration's
duration minus what the worker calls inside it (prefill, chunk, decode,
verify) cover. Steps in which a call compiled are left out."""
import numpy as np

from . import span_ring as R

NAME, UNIT, LAYER = "sched_host_ms_p50", "ms", "serving scheduler"


def read(ctx):
    spans = R.started_in(R.serve_window(ctx))
    by_id = {sp.id: sp for sp in spans}
    steps = {sp.id: sp.t1 - sp.t0 for sp in spans if R.is_a(sp, "serve/step")}
    for sp in spans:
        if not any(R.is_a(sp, k) for k in R.WORKER_CALLS):
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
