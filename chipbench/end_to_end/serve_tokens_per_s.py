"""Prompt plus generated tokens of the requests that completed ``ok`` inside
the window, over the window (closed loop: capacity, not luck)."""
NAME, UNIT = "serve_tokens_per_s", "tokens/s"


def read(ctx):
    s = ctx["serve"]
    if not s or s["loop"] != "closed":
        return None
    tokens = sum(r["prompt_len"] + r["n_out"] for r in s["completed"])
    return tokens / ctx["window_s"]
