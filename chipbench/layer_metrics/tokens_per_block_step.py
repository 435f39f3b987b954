"""Tokens revealed a slot a block step over the host window: the positions the steps'
passes revealed (the spans' ``revealed``) over the (active slot, step) pairs. A block of B
positions takes T denoising passes and a commit, so a full schedule reads B / (T + 1): 0.8
at B = T = 4. The number that says a device step is no longer a token."""
from . import block_calls as B

NAME, UNIT, LAYER = "tokens_per_block_step", "tokens", "serving scheduler"


def read(ctx):
    found = B.calls(ctx, "window")
    pairs = sum(c.attrs["slots_active"] for c in found)
    return sum(c.attrs["revealed"] for c in found) / pairs if pairs else None
