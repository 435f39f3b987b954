"""What the readers of the CHUNK programs share (no metric of its own).

A prompt admitted in chunks is a run of ``.../chunk`` spans (``SlotWorker.chunk``),
all but the last of them left ASYNCHRONOUS (``fetch: false``): such a span ends when
its program is enqueued, not when the device is done, and the decode step behind it
waits for both. So a chunk's time is never its span's: it is the device's own
seconds of the chunk programs (the trace names their operations
``jit_chunk/<instruction>``), over the chunk calls that began in the traced window.
A trace that names no program (the CPU's: a rehearsal, which prints no number) has no
such time; there the spans' own stand in, so that a rehearsal still drives every cost
function and lists the metric. A program whose chunk spans lack what a reader needs
(before PR 59: ``start``, ``whole_keys``) gives it nothing to read.
"""
from . import call_anatomy as A
from . import span_ring as R

PROGRAM = "jit_chunk"
NEEDS = ("start", "width", "whole_keys", "ring_tokens", "expert_rows_held")


def calls(ctx, window: str = "traced") -> list:
    """The chunk calls (none that compiled) that began in the window, with every
    attribute of ``NEEDS`` on their span."""
    if not ctx["serve"]:
        return []
    found = R.calls(R.started_in(R.serve_window(ctx, window)), "chunk")
    return [call for call, _, _ in found if all(key in call.attrs for key in NEEDS)]


def device_seconds(ctx, found) -> float:
    """The device's seconds of the chunk programs in the traced window, or the spans'
    own where the trace names no program."""
    by_program = A.program_seconds(ctx)
    return by_program.get(PROGRAM, 0.0) if by_program else sum(c.t1 - c.t0 for c in found)
