"""The share of the device's working time that the chunk programs take in the traced
window: the trace's seconds of ``jit_chunk/...`` operations over the seconds of every
program's (chunks, decode steps, what else ran). What the scheduler's interleaving
(``chunks_per_step`` chunks a step beside one decode step) makes of the traffic: a
cell whose prompts cost more than its outputs reads over half. The call spans cannot
say it: an intermediate chunk's ends at its enqueue (``chunk_calls.py``). A trace that
names no program (the CPU's) gives the spans' share of the traced window's worker
calls instead, so a rehearsal lists the metric. Absent where no chunk ran."""
from . import call_anatomy as A
from . import chunk_calls as C
from . import span_ring as R

NAME, UNIT, LAYER = "chunk_share_of_loop_pct", "%", "serving scheduler"


def read(ctx):
    found = C.calls(ctx)
    if not found:
        return None
    by_program = A.program_seconds(ctx)
    if by_program:
        chunks, total = by_program.get(C.PROGRAM, 0.0), sum(by_program.values())
    else:  # no program is named: the CPU, whose numbers are never printed
        spans = R.calls(R.started_in(R.serve_window(ctx, "traced")), *R.WORKER_CALLS)
        chunks = sum(c.t1 - c.t0 for c in found)
        total = sum(call.t1 - call.t0 for call, _, _ in spans)
    if total <= 0 or chunks <= 0:
        return None
    ctx["run"].note(event="device_share", program="chunk", chunk_s=chunks, programs_s=total,
                    chunks=len(found))
    return 100.0 * chunks / total
