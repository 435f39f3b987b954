"""Median host-clock time of one ``train_batch`` + ``block_until_ready``."""
import numpy as np

NAME, UNIT, LAYER = "train_step_ms_p50", "ms", "training engine"


def read(ctx):
    t = ctx["train"]
    return 1e3 * float(np.median([b - a for a, b in t["steps"]])) if t else None
