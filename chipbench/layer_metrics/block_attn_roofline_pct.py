"""Roofline share of the operations that attend in the block-step program: the least time
the chip could take for the attention the traced steps REQUIRE (``block_cost.
attention_cost``: every row of a slot's block against the ``pos + B`` keys it sees, in every
layer; q, o, and the K/V of those positions; the bound is printed) over the summed device
time of the operations that implement it, found in the trace by the HLO text it keeps of
each operation of ``jit_block_step``: those that make or read the block's SCORES, [slots,
query heads x B rows, Smax] (or [slots, heads, B, Smax]) in float32, the model's dtype or
as the mask's booleans: the contraction of the rows of q against the cache's rows, the
mask laid out over the scores, the masked softmax, and the contraction of the
probabilities against the values. The block's K/V scatter into the cache
and the head-layout moves round them are the program's and not these operations':
``block_step_hbm_floor_pct`` pays for them. Absent where no such operation ran."""
import re

from .. import block_cost, flops
from . import block_calls as B

NAME, UNIT, LAYER = "block_attn_roofline_pct", "%", "kernels"


def score_ops(tr, program, slots: int, smax: int) -> dict:
    """{operation: seconds} of the block-step program's operations whose text names the
    scores' shape."""
    heads, block = program["num_heads"], program["attn_block_length"]
    shapes = (rf"\[{slots},{heads * block},{smax}\]", rf"\[{slots},{heads},{block},{smax}\]")
    rx = re.compile(r"(?:f32|bf16|pred)(?:" + "|".join(shapes) + ")")
    return {name: seconds for name, seconds in tr["op_seconds"].items()
            if name.startswith(B.PROGRAM + "/") and rx.search(tr.get("op_text", {}).get(name, ""))}


def read(ctx):
    tr = ctx["trace"]
    found = B.calls(ctx)
    if not tr or not found or "attn_block_length" not in ctx["program"]:
        return None
    smax = -(-int(ctx["program"]["max_seq_len"]) // 128) * 128
    slots = int(found[0].attrs["rows"]) // int(ctx["program"]["attn_block_length"])
    ops = score_ops(tr, ctx["program"], slots, smax)
    seconds = sum(ops.values())
    if seconds <= 0:
        return None
    costs = [block_cost.attention_cost(ctx["program"], c.attrs["rows"], c.attrs["live_keys"])
             for c in found]
    cost = {k: sum(c[k] for c in costs) for k in ("flops", "bytes")}
    share = flops.roofline(cost, seconds, ctx["peak"])
    ctx["run"].note(event="roofline", kernel="block attention", seconds=seconds,
                    steps=len(found), operations=len(ops), **cost, **share)
    return share["pct"]
