"""Process start to the start of the measured window: imports, weights,
engine build, warm-up of the cell's own shapes, the correctness check and,
for request traffic, the lead-in that fills the slots."""
NAME, UNIT = "setup_s", "s"


def read(ctx):
    return ctx["t_setup"]
