"""What the readers of a block step share (no metric of its own): the ``.../block_step``
spans of a window (``SlotWorker.block_step``: generation by diffusion over blocks) and the
device's seconds of their program. A program without such a span (every one before PR 63,
every model with ``attn_block_length`` 1) gives every reader nothing to read: ``None``,
and the metric is left out of the line."""

from . import call_anatomy as A
from . import span_ring as R

PROGRAM = "jit_block_step"
# the worker's device programs of a model that generates by blocks: ``span_ring``'s and the
# block step (``span_ring.WORKER_CALLS`` is the accepted readers' own and is left as it is)
WORKER_CALLS = (*R.WORKER_CALLS, "block_step")
NEEDS = ("rows", "slots_active", "masked_rows", "revealed", "commits", "live_keys")


def calls(ctx, window: str = "traced") -> list:
    """The block-step calls (none that compiled) that began in the window, with every
    attribute of ``NEEDS`` on their span."""
    if not ctx["serve"] or R.serve_window(ctx, window) is None:
        return []
    found = R.calls(R.started_in(R.serve_window(ctx, window)), "block_step")
    return [call for call, _, _ in found if all(key in call.attrs for key in NEEDS)]


def device_ms(ctx, found):
    """The device's own milliseconds a run of the block-step program over the traced
    window's calls ``found``; None where the trace names no such program (the CPU's)."""
    seconds = A.program_seconds(ctx).get(PROGRAM)
    return 1e3 * seconds / len(found) if seconds and found else None

