"""Roofline share of the grouped matmuls of the routed layers in the CHUNK programs
(``jax.lax.ragged_dot``: the trace names them ``jit_chunk/ragged-dot...``):
``moe_gemm_roofline_pct``'s twin for a cell whose prompts enter in chunks, where that
reader, which matches ``prefill`` programs and ``.../prefill`` spans, finds nothing.
The least time the chip could take for the grouped matmuls of the traced window's
chunks (``moe_cost.grouped_gemm_cost`` over each ``.../chunk`` span's ``width``: the
pairs of its padded rows are multiplied like any other, and every expert's weights
are read once a call whatever the width, which is what a short tail pays for; the
bound is printed) over those operations' summed device time in the trace. Absent
where no such operation ran (a cell that admits whole prompts, a model without routed
layers, a trace that names no program)."""
from .. import flops, moe_cost
from ..reduce import op_seconds_matching
from . import chunk_calls as C

NAME, UNIT, LAYER = "chunk_moe_gemm_roofline_pct", "%", "kernels"
KERNELS = r"chunk\S*/ragged-dot"  # by the operation's name: <program>/<instruction>


def read(ctx):
    tr = ctx["trace"]
    if not tr or "moe_top_k" not in ctx["program"]:
        return None
    seconds = op_seconds_matching(tr, KERNELS)
    found = C.calls(ctx)
    if seconds <= 0 or not found:
        return None
    costs = [moe_cost.grouped_gemm_cost(ctx["program"], c.attrs["width"]) for c in found]
    cost = {k: sum(c[k] for c in costs) for k in ("flops", "bytes")}
    share = flops.roofline(cost, seconds, ctx["peak"])
    ctx["run"].note(event="roofline", kernel="chunk ragged-dot", seconds=seconds,
                    chunks=len(found), rows=sum(c.attrs["width"] for c in found),
                    live=sum(c.attrs["live"] for c in found), **cost, **share)
    return share["pct"]
