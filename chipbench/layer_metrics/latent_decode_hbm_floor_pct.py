"""The decode program's share of its memory roofline for a model that caches a
latent: the least time the chip needs to read what one decode step must
(``mla_cost.decode_min_bytes``: the matmul weights outside the experts, the
experts the step touched, the live latent cache at ``kv_lora_rank +
qk_rope_head_dim`` values a token a layer) over the device's own time a run of
the decode program (``decode_floor.py``). The experts touched and the live
tokens are the medians of the traced calls' own ``experts_touched`` and
``cached_tokens``; a program whose spans lack either (one from before them)
gives nothing. The harness's own count of live tokens
(``ctx["serve"]["steps"]``) is printed beside the span's."""
from .. import mla_cost
from . import decode_floor as F

NAME, UNIT, LAYER = "latent_decode_hbm_floor_pct", "%", "serving device programs"
NEEDS = ("cached_tokens", "experts_touched")


def read(ctx):
    if "kv_lora_rank" not in ctx["program"]:
        return None
    calls = F.calls(ctx, NEEDS)
    if not calls:
        return None
    cached, touched = (F.median(calls, key) for key in NEEDS)
    need = mla_cost.decode_min_bytes(ctx["program"], cached, touched)
    return F.share(ctx, calls, need, live_tokens=cached, experts_touched=touched,
                   harness_live_tokens=F.live_tokens(ctx))
