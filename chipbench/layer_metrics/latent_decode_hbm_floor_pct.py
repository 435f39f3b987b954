"""The decode program's share of its memory roofline for a model that caches a
latent: the least time the chip needs to read what one decode step must
(``mla_cost.decode_min_bytes``: the matmul weights outside the experts, the
experts the step touched, the live latent cache at ``kv_lora_rank +
qk_rope_head_dim`` values a token a layer) over the median ``.../decode``
span. The experts touched and the live tokens are the medians of the spans'
own ``experts_touched`` and ``cached_tokens``; a program whose spans lack
either (one from before them) gives nothing. The harness's own count of live
tokens (``ctx["serve"]["steps"]``) is printed beside the span's."""
import numpy as np

from .. import mla_cost
from . import span_ring as R

NAME, UNIT, LAYER = "latent_decode_hbm_floor_pct", "%", "serving device programs"


def read(ctx):
    s = ctx["serve"]
    if not s or "kv_lora_rank" not in ctx["program"]:
        return None
    calls = [call for call, _, _ in R.calls(R.started_in(R.serve_window(ctx)), "decode")]
    calls = [c for c in calls if "experts_touched" in c.attrs and "cached_tokens" in c.attrs]
    if not calls:
        return None
    touched = float(np.median([c.attrs["experts_touched"] for c in calls]))
    cached = float(np.median([c.attrs["cached_tokens"] for c in calls]))
    lo, hi = s["window"]
    harness = [n for ts, te, _, n in s["steps"] if lo <= ts and te <= hi and n > 0]
    need = mla_cost.decode_min_bytes(ctx["program"], cached, touched)
    floor_ms = 1e3 * need / ctx["peak"]["hbm_bytes_per_s"]
    step_ms = R.median_ms(calls)
    ctx["run"].note(event="roofline", program="decode", floor_ms=floor_ms, step_ms=step_ms,
                    bytes=need, experts_touched=touched, cached_tokens=cached,
                    harness_live_tokens=float(np.median(harness)) if harness else None)
    return 100.0 * floor_ms / step_ms
