"""The block-step program's share of its memory roofline: the least time the chip needs
to read what one step must (``block_cost.step_min_bytes``: the matmul weights outside the
experts, the banks of the experts its rows chose, the K/V of the positions its blocks see)
over the device's own time a run of the program (``block_step_device_ms_mean``). The counts
are the medians of the traced calls' own ``live_keys`` and ``experts_touched``."""
from .. import block_cost
from . import block_calls as B
from .decode_floor import median

NAME, UNIT, LAYER = "block_step_hbm_floor_pct", "%", "serving device programs"


def read(ctx):
    found = B.calls(ctx)
    device_ms = B.device_ms(ctx, found)
    touched = median(found, "experts_touched")
    if not device_ms or touched is None or "attn_block_length" not in ctx["program"]:
        return None
    live_keys = median(found, "live_keys")
    need = block_cost.step_min_bytes(ctx["program"], live_keys, touched)
    floor_ms = 1e3 * need / ctx["peak"]["hbm_bytes_per_s"]
    ctx["run"].note(event="roofline", program="block_step", floor_ms=floor_ms,
                    device_ms=device_ms, bytes=need, live_keys=live_keys,
                    experts_touched=touched, steps=len(found))
    return 100.0 * floor_ms / device_ms
