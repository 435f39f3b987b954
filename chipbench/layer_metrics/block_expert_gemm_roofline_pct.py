"""Roofline share of the operations that run the routed experts in the block-step program:
the least time the chip could take for the experts' three matmuls the traced steps REQUIRE
(``block_cost.expert_gemm_cost``: 2 operations a parameter a (row, chosen expert) pair of
the ACTIVE slots' rows; the banks of the experts those rows chose, read once a layer, and
the pairs' activations; the bound is printed) over the summed device time of the operations
that implement them, whichever form the program took (every expert over every row, or the
pairs sorted by expert through the grouped kernel): the operations of ``jit_block_step``
whose HLO text names an expert bank as an operand, [experts, hidden, width] or [experts,
width, hidden] in the model's dtype, stacked over the layers or not. Absent where no such
operation ran, and where the spans carry no ``experts_touched``."""
import re

from .. import block_cost, flops
from . import block_calls as B

NAME, UNIT, LAYER = "block_expert_gemm_roofline_pct", "%", "kernels"


def bank_ops(tr, program) -> dict:
    """{operation: seconds} of the block-step program's operations that read a bank."""
    E, d, f = program["num_experts"], program["hidden_size"], program["intermediate_size"]
    rx = re.compile(rf"bf16\[(?:\d+,)?{E},(?:{d},{f}|{f},{d})\]")
    text = tr.get("op_text", {})
    return {name: seconds for name, seconds in tr["op_seconds"].items()
            if name.startswith(B.PROGRAM + "/") and "/while" not in name  # (a loop names its carry)
            and rx.search(text.get(name, ""))}


def read(ctx):
    tr = ctx["trace"]
    found = [c for c in B.calls(ctx) if "experts_touched" in c.attrs]
    if not tr or not found or "attn_block_length" not in ctx["program"]:
        return None
    ops = bank_ops(tr, ctx["program"])
    seconds = sum(ops.values())
    if seconds <= 0:
        return None
    B_ = int(ctx["program"]["attn_block_length"])
    costs = [block_cost.expert_gemm_cost(ctx["program"], c.attrs["slots_active"] * B_,
                                         c.attrs["experts_touched"]) for c in found]
    cost = {k: sum(c[k] for c in costs) for k in ("flops", "bytes")}
    share = flops.roofline(cost, seconds, ctx["peak"])
    ctx["run"].note(event="roofline", kernel="block experts", seconds=seconds,
                    steps=len(found), operations=len(ops), **cost, **share)
    return share["pct"]
