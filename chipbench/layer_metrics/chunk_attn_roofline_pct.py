"""Roofline share of the operations that attend in the chunk programs of a model with
window and whole-context layers: the least time the chip could take for the attention
the traced chunks REQUIRE (``chunk_cost.attention_cost``: ``start + i`` keys a query in
a whole-context layer, min(., window) in a window layer; q, o, and the keys and values
a chunk must read; the bound is printed) over the summed device time of the operations
that implement it, found in the trace by name and by text:

- a window layer's band over [ring ; chunk]: the flash forward, ``jit_chunk/flash_fwd...``;
- a whole-context layer's walk over the prefix (``transformer._blocks_attention``, XLA):
  the operations of the chunk programs whose result is one of the walk's own arrays, the
  running maximum and sum [1, K/V heads, group, rows] and the accumulator [1, K/V heads,
  group, rows, head width] in float32 or the model's dtype (the scores of a key block
  stay inside their fusions), by the HLO text the trace keeps of each operation; the
  loop's own ``while`` carries them and counts with its self time.

What XLA does round them (the slot's window sliced out and viewed as heads, the ring's
gather, the scatter of the chunk's keys) is the program's and not these operations':
``chunk_prefill_mfu_pct`` pays for it. Absent where neither ran (a cell that admits
whole prompts; a trace that names no program), and where chunks entered behind a prefix
and the walk's operations were not found (a share over the band's time alone would
count work against time that left it out)."""
import re

from .. import chunk_cost, flops
from ..reduce import op_seconds_matching
from . import chunk_calls as C

NAME, UNIT, LAYER = "chunk_attn_roofline_pct", "%", "kernels"
BAND = r"chunk\S*/flash_fwd"  # by the operation's name: <program>/<instruction>


def walk_seconds(tr, program) -> float:
    """Device seconds of the walk's operations in the chunk programs (module docstring)."""
    kv = program["num_kv_heads"]
    rows = rf"\[1,{kv},{program['num_heads'] // kv},\d+(?:,{program['qk_head_dim']})?\]"
    rx = re.compile(rf"(?:f32|bf16){rows}")
    return sum(seconds for name, seconds in tr["op_seconds"].items()
               if name.startswith(C.PROGRAM + "/") and rx.search(tr["op_text"].get(name, "")))


def read(ctx):
    tr = ctx["trace"]
    if not tr or "local_attn_layers" not in ctx["program"]:
        return None
    band, walk = op_seconds_matching(tr, BAND), walk_seconds(tr, ctx["program"])
    found = C.calls(ctx)
    if band + walk <= 0 or not found:
        return None
    if walk <= 0 and any(c.attrs["start"] > 0 for c in found):
        return None
    costs = [chunk_cost.attention_cost(ctx["program"], c.attrs["start"], c.attrs["width"],
                                       c.attrs["whole_keys"], c.attrs["ring_tokens"])
             for c in found]
    cost = {k: sum(c[k] for c in costs) for k in ("flops", "bytes")}
    share = flops.roofline(cost, band + walk, ctx["peak"])
    ctx["run"].note(event="roofline", kernel="chunk walk+flash_fwd", seconds=band + walk,
                    band_seconds=band, walk_seconds=walk, chunks=len(found), **cost, **share)
    return share["pct"]
