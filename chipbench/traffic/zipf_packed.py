"""Training batches: a Zipf(alpha) token stream cut into full sequences with
no document mask (as Pythia was trained on packed text); a fresh batch every
step, the whole stream a function of the seed."""

from __future__ import annotations

import numpy as np

LOOP = "batches"
# the parameters a cell's ``traffic`` block gives this generator
EXAMPLE = {"kind": "zipf_packed", "zipf_alpha": 1.1, "sequences_per_step": 32,
           "sequence_length": 2048}


def generate(params: dict, *, seed: int, vocab_size: int, **_) -> dict:
    rng = np.random.default_rng([int(seed), 0x21BF])
    weights = 1.0 / np.arange(1, vocab_size + 1, dtype=np.float64) ** float(params["zipf_alpha"])
    cdf = np.cumsum(weights / weights.sum())
    shape = (int(params["sequences_per_step"]), int(params["sequence_length"]) + 1)

    def batches():
        while True:
            yield np.minimum(np.searchsorted(cdf, rng.random(shape)),
                             vocab_size - 1).astype(np.int32)

    return {"loop": LOOP, "batches": batches(), "tokens_per_step": shape[0] * (shape[1] - 1)}
