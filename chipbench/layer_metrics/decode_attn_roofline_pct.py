"""Roofline share of the Pallas decode-attention kernel: the least time the
chip could take to read the keys and values actually LIVE in each traced decode
step (``flops.decode_attention_cost``; memory-bound), in every cache layer, over
the kernel's summed device time in the trace. The kernel is found by the name it
gives its call (the trace names the decode program's operations
``jit_decode/decode_attention.N``), so the grouped GEMMs and the flash forward of
a prefill, Pallas calls too, count nothing here. A cache layer is one (pass,
layer): ``loop_cost.passes`` x ``num_layers``, 48 for 12 layers run four times.
The cost is of the live tokens, not of the blocks a kernel fetches, so the share
reads the same work whatever implements the kernel. Absent where no such
operation ran (alibi, grouped heads, ``"decode_attn": "xla"``, training)."""
import re

from .. import flops, loop_cost

NAME, UNIT, LAYER = "decode_attn_roofline_pct", "%", "kernels"
KERNELS = re.compile(r"^\S*decode\S*/decode_attention")  # <program>/<instruction>, by NAME alone


def read(ctx):
    tr, s = ctx["trace"], ctx["serve"]
    if not tr or not s or s["traced"][0] is None:
        return None
    seconds = sum(v for name, v in tr["op_seconds"].items() if KERNELS.match(name))
    if seconds <= 0:
        return None  # the kernel did not run here
    t0, t1 = s["traced"]
    live = sum(n for ts, te, _, n in s["steps"] if t0 <= ts and te <= t1)
    p = ctx["program"]
    layers = loop_cost.passes(p) * p["num_layers"]
    cost = flops.decode_attention_cost(live, p["num_heads"], loop_cost.head_dim(p), layers)
    share = flops.roofline(cost, seconds, ctx["peak"])
    ctx["run"].note(event="roofline", kernel="decode_attention", seconds=seconds,
                    cache_layers=layers, live_tokens=live, **share)
    return share["pct"]
