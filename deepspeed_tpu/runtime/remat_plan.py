"""What a layer's activation checkpoint saves beside its floor, chosen from
shapes and the device's memory (ROADMAP S1(a), D4).

``remat_policy: "save_flash"`` is the LEAST a checkpointed layer saves: its
input and the flash kernel's output. Everything else the backward pass reads
is recomputed, the feed-forward's up projection among it: 4 of the 43 units of
d x d matmul work a token that a parallel-residual layer's step runs. Where the
device has room beside the training state, the gradients, the floor's residuals
and the step's temporaries, the engine keeps that product's output
(``models/transformer.remat_candidates``) and the backward pass reads it
instead. ``plan_saved`` is that choice as arithmetic: a function of the device's
memory LIMIT and of shapes alone, never of what is in use at the moment, so a
step built twice is one program, and a step built on every host of a
multi-process run is one program too.
"""

from __future__ import annotations

from dataclasses import dataclass

# What the plan leaves beside its own count of the step's bytes. The count's
# parts are shapes (the state and the gradients from the shardings, the
# residuals from the traced micro-batch, the temporaries from
# ``models/transformer.step_working_bytes``); the count of the temporaries read
# between 2% under and 9% over the compiled floor program's at seventeen shapes
# of the ZeRO-3 step (experiments/remat_fit.py; PERF.md section 6, PR 50), so a
# tenth more of them is kept free. A step that needs more still fails to compile
# for memory and the engine builds the floor program (``_dispatch_step``).
HEADROOM = 1.1


@dataclass(frozen=True)
class RematPlan:
    """``names`` to save beside the floor policy's (none: the candidate does not
    fit, or there is no limit), their ``saved_bytes`` a device, and the ``room``
    the arithmetic found for them."""

    names: tuple = ()
    saved_bytes: int = 0
    room: int = 0


def plan_saved(limit, held: int, working: int, names, candidate: int) -> RematPlan:
    """``names`` where the ``candidate``'s bytes fit a device, nothing otherwise.

    ``limit``: the device's memory limit in bytes (``memory_stats()["bytes_limit"]``;
    None or 0 where the platform gives none: nothing is added). ``held``: what
    the device holds through the step whatever is chosen: its shard of the
    training state and the floor policy's residuals. ``working``: the step's
    temporaries (the gradients, ``step_working_bytes``), which get ``HEADROOM``."""
    if not limit or not candidate:
        return RematPlan()
    room = int(limit - held - HEADROOM * working)
    return RematPlan(tuple(names), candidate, room) if candidate <= room else RematPlan(room=room)
