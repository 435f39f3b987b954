"""Closed-loop request traffic: ``clients_per_slot * n_slots`` clients, each
sending its next request the moment its last one completes; greedy.

Requests are handed out in one global order to whichever client is free, and
the lengths are seeded permutations of the distribution's evenly spaced
quantiles, block after block, so every stretch of a run sees the same mean
length whatever the seed. ``max_rps`` only sizes the pre-generated list (an
upper bound on what the system could complete).
"""

from __future__ import annotations

import numpy as np

from .lengths import quantile_lengths

LOOP = "closed"
_BLOCK = 64
# the parameters a cell's ``traffic`` block gives this generator
EXAMPLE = {"kind": "closed_loop", "clients_per_slot": 2, "max_rps": 40, "lead_in_s": 5,
           "grace_s": 5, "prompt": {"dist": "uniform", "min": 1024, "max": 1920},
           "output": {"dist": "uniform", "min": 8, "max": 32}, "max_total": 2048}


def _block_lengths(rng, n: int, spec) -> np.ndarray:
    block = quantile_lengths(_BLOCK, spec)
    return np.concatenate([rng.permutation(block) for _ in range(-(-n // _BLOCK))])[:n]


def generate(params: dict, *, seed: int, seconds: float, vocab_size: int,
             n_slots: int, **_) -> dict:
    rng = np.random.default_rng([int(seed), 0xD0C])
    lead = float(params["lead_in_s"])
    n = int(params["max_rps"] * (lead + seconds)) + _BLOCK
    prompt = _block_lengths(rng, n, params["prompt"])
    output = _block_lengths(rng, n, params["output"])
    output = np.maximum(np.minimum(output, params["max_total"] - prompt), 1)
    requests = [{
        "uid": i,
        "prompt": rng.integers(0, vocab_size, size=int(prompt[i])).astype(np.int32),
        "max_new_tokens": int(output[i]),
        "temperature": 0.0, "top_p": 1.0, "arrival_time": 0.0,
    } for i in range(n)]
    return {"loop": LOOP, "requests": requests, "window": (lead, lead + seconds),
            "clients": int(params["clients_per_slot"] * n_slots)}
