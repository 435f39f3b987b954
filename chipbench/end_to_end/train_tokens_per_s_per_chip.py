"""Tokens of the whole steps that ended inside the window, over the time from
the window's start to the end of the last of them, per chip. Every step ends
in ``block_until_ready``."""
NAME, UNIT = "train_tokens_per_s_per_chip", "tokens/s"


def read(ctx):
    t = ctx["train"]
    if not t:
        return None
    return len(t["steps"]) * t["tokens_per_step"] / ctx["window_s"] / ctx["chips"]
