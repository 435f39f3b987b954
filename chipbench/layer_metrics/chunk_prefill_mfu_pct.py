"""The chunk programs' share of the chip's peak for a model with window and
whole-context layers whose prompts enter in chunks: the operations the traced
window's chunks require (``chunk_cost.chunk_flops`` of each ``.../chunk`` span's
``width``, ``expert_rows_held``, ``whole_keys`` and ``ring_tokens``: the parameters
outside the experts x the chunk's rows, the head for one row, one expert's parameters
x the pairs dispatched, attention at ``start + i`` keys a query in a whole-context
layer and min(., window) in a window layer) over peak FLOP/s and the DEVICE's seconds
of the chunk programs in that window (``chunk_calls.py``: an intermediate chunk's
span ends at its enqueue, so the spans' own time is no chunk's). An end-to-end
utilisation of those programs, a tail's padding counted as work. Absent where no
chunk ran (a cell that admits whole prompts) or its spans lack the counts."""
from .. import chunk_cost
from . import chunk_calls as C

NAME, UNIT, LAYER = "chunk_prefill_mfu_pct", "%", "serving device programs"


def read(ctx):
    if "local_attn_layers" not in ctx["program"]:
        return None
    found = C.calls(ctx)
    seconds = C.device_seconds(ctx, found) if found else 0.0
    if seconds <= 0:
        return None
    flops = sum(chunk_cost.chunk_flops(
        ctx["program"], c.attrs["width"], c.attrs["expert_rows_held"], c.attrs["whole_keys"],
        c.attrs["ring_tokens"]) for c in found)
    ctx["run"].note(event="roofline", program="chunk", chunks=len(found), seconds=seconds,
                    flops=flops, rows=sum(c.attrs["width"] for c in found),
                    live=sum(c.attrs["live"] for c in found),
                    whole_keys=sum(c.attrs["whole_keys"] for c in found),
                    ring_tokens=sum(c.attrs["ring_tokens"] for c in found))
    return 100.0 * flops / ctx["peak"]["bf16_flops_per_s"] / seconds
