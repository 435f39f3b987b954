#!/usr/bin/env python3
"""By hand, on the chip (PR 38): the multi-token-prediction module's forward pass
at K-EXAONE-236B-A23B's published widths (``chipbench/configs/
k-exaone-236b-a23b-L5.json``'s ``program`` with ``mtp_layers: 1``; bf16 compute on
held weights) against the plain float32 reference, given the system's own
routing. No cell runs the module (no serving program reads it yet): this is the
one reading PERF.md section 6 quotes.

    chiprun -- python3 experiments/mtp_by_hand.py [rows] [seed]
"""
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench.references import Program, load_reference  # noqa: E402
from deepspeed_tpu.models import transformer as tfm  # noqa: E402


def main(rows: int = 2048, seed: int = 0) -> None:
    with open(os.path.join(ROOT, "chipbench", "configs", "k-exaone-236b-a23b-L5.json")) as f:
        program = Program({**json.load(f)["program"], "mtp_layers": 1}, "exaone_moe")
    reference = load_reference(program)
    cfg = tfm.TransformerConfig(dtype=jnp.bfloat16, **program)
    params = jax.jit(lambda r: tfm.hold_for_compute(cfg, tfm.init(cfg, r)))(jax.random.PRNGKey(seed))
    held = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    tokens = np.random.default_rng(seed).integers(0, cfg.vocab_size, rows + 1).astype(np.int32)
    fwd = jax.jit(lambda p, t, n: tfm.apply(cfg, p, t, return_routing=True, mtp_tokens=n))
    t0 = time.perf_counter()
    logits, chosen, mtp = jax.block_until_ready(fwd(params, tokens[None, :-1], tokens[None, 1:]))
    t1 = time.perf_counter()
    jax.block_until_ready(fwd(params, tokens[None, :-1], tokens[None, 1:]))
    t2 = time.perf_counter()
    at = np.arange(rows)
    routing = np.asarray(chosen)[:, 0]
    whole = lambda leaves: leaves  # noqa: E731
    want = reference.mtp_logits_at(program, params, tokens, at, fetch=whole, routing=routing)
    free = reference.mtp_logits_at(program, params, tokens, at, fetch=whole)
    main_want = reference.logits_at(program, params, tokens[:-1], at, fetch=whole,
                                    routing=routing[:-1])
    got = np.asarray(mtp[0], np.float32)
    print(json.dumps({
        "device": jax.devices()[0].device_kind, "rows": rows, "seed": seed,
        "held_gb": held / 1e9, "first_call_s": t1 - t0, "second_call_s": t2 - t1,
        "mtp_logit_max_abs_err": float(np.max(np.abs(got - want))),
        "mtp_logit_max_abs_err_free_routing": float(np.max(np.abs(got - free))),
        "main_logit_max_abs_err": float(np.max(np.abs(np.asarray(logits[0], np.float32)
                                                      - main_want))),
        "reference_mtp_logit_std": float(np.std(want)),
        "mtp_top_agrees_share": float(np.mean(got.argmax(-1) == want.argmax(-1))),
        "hbm_peak_gb": (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0) / 1e9}))


if __name__ == "__main__":
    main(*(int(a) for a in sys.argv[1:3]))
