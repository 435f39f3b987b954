"""``moe_load_max_over_mean`` for a model that generates by diffusion over blocks: the
median over the window's ``.../block_step`` spans of their ``expert_load_max_over_mean``: in
the worst routed layer of a block step, the live rows sent to the busiest expert over those
sent to the mean expert (1 = even; the weights are random, so it says nothing about a
trained router). How uneven the routing was that the step times were taken under. Absent
where no block step's span carries the attribute."""
import numpy as np

from . import block_calls as B

NAME, UNIT, LAYER = "block_moe_load_max_over_mean", "ratio", "model"
ATTR = "expert_load_max_over_mean"


def read(ctx):
    loads = [call.attrs[ATTR] for call in B.calls(ctx, "window") if ATTR in call.attrs]
    return float(np.median(loads)) if loads else None
