"""Open-loop request traffic: arrivals on a schedule whether or not earlier
requests have finished, lengths from the cell's distributions, no shared
prefixes.

The amount of work in the measured window is fixed by the parameters, not by
the seed: the window holds exactly ``round(rate_rps * seconds)`` requests,
their lengths are the evenly spaced quantiles of the cell's distributions,
and the seed decides only their order, their pairing, the arrival instants
(sorted uniforms: a Poisson process given its count) and the token values. Two seeds offer the same tokens and differ in
burstiness alone, which is what lets a tail repeat within a few percent in
under a minute.

A lead-in of ``lead_in_s`` seconds at the same rate (lengths drawn at random)
fills the slots before the window opens; its requests are served and not
counted.
"""

from __future__ import annotations

import numpy as np

from .lengths import quantile_lengths, random_lengths

LOOP = "open"


# the parameters a cell's ``traffic`` block gives this generator (the chat mix
# of ISSUE 23; ``selftest.py`` draws from it)
EXAMPLE = {"kind": "open_loop", "rate_rps": 2.0, "lead_in_s": 5, "grace_s": 5,
           "prompt": {"dist": "lognormal", "median": 256, "sigma": 0.9, "min": 32, "max": 1536},
           "output": {"dist": "lognormal", "median": 128, "sigma": 0.7, "min": 16, "max": 512},
           "max_total": 2048, "sampled_share": 0.5, "temperature": 0.7, "top_p": 0.9}


def _arrival_times(rng, n: int, t0: float, t1: float) -> np.ndarray:
    """n sorted arrival instants in [t0, t1): a Poisson process given its count."""
    return t0 + np.sort(rng.uniform(0.0, 1.0, n)) * (t1 - t0)


def generate(params: dict, *, seed: int, seconds: float, vocab_size: int, **_) -> dict:
    rng = np.random.default_rng([int(seed), 0x0CA7])
    rate, lead = float(params["rate_rps"]), float(params["lead_in_s"])
    n_win, n_lead = int(round(rate * seconds)), int(round(rate * lead))
    prompt = np.concatenate([random_lengths(rng, n_lead, params["prompt"]),
                             rng.permutation(quantile_lengths(n_win, params["prompt"]))])
    output = np.concatenate([random_lengths(rng, n_lead, params["output"]),
                             rng.permutation(quantile_lengths(n_win, params["output"]))])
    output = np.maximum(np.minimum(output, params["max_total"] - prompt), 1)
    arrival = np.concatenate([
        _arrival_times(rng, n_lead, 0.0, lead),
        _arrival_times(rng, n_win, lead, lead + seconds)])
    # exactly the stated share is sampled, spread evenly over arrival order
    n, share = n_lead + n_win, float(params["sampled_share"])
    sampled = np.floor((np.arange(n) + 1) * share) > np.floor(np.arange(n) * share)
    requests = [{
        "uid": i,
        "prompt": rng.integers(0, vocab_size, size=int(prompt[i])).astype(np.int32),
        "max_new_tokens": int(output[i]),
        "temperature": float(params["temperature"]) if sampled[i] else 0.0,
        "top_p": float(params["top_p"]) if sampled[i] else 1.0,
        "arrival_time": float(arrival[i]),
    } for i in range(n)]
    return {"loop": LOOP, "requests": requests, "window": (lead, lead + seconds)}
