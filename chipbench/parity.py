#!/usr/bin/env python3
"""The parity harness: the system's model against the plain reference its
configuration names, for every ``configs/*.json`` at its ``rehearse_program``
size, on the CPU, float32 on both sides, on seeded weights.

    python3 -m chipbench.parity              # every case, exit 0 if they hold

``CHECKS`` are the three surfaces the chip's checks lean on:

``apply``  ``transformer.apply``'s logits against ``logits_at`` at every row.
``cache``  what serving computes: ``drivers/serve.py::probe_logits`` (the
           probe of the chip's check: bucket-padded prefill of two prompts
           into a slot cache, then 8 decode steps through it at per-row
           positions) against the reference's full forward pass.
``loss``   ``causal_lm_loss`` against ``lm_loss``.

A configuration added to ``configs/`` is a case of each with no edit here
(``cases()`` is what a pytest ``parametrize`` takes). Every leaf is seeded
noise round ``init``'s value, so biases and LayerNorm offsets, which ``init``
leaves at zero, count too.

``TOL``: both sides compute in float32 and differ by summation order alone.
Measured here (CPU, PR 26): logits within 9.5e-7 (bloom-1b7, logits of
standard deviation 0.33) and 2.4e-6 (pythia-1.4b, 1.05) of the reference, the
loss within 4.8e-7 (one unit in the last place of a loss of 6.6). With
bfloat16 compute on the system's side the same cases read 1.1e-2 to 3.6e-2
on the logits and 3.8e-4 to 1.4e-3 on the loss. So 1e-4 on the logits passes
float32 with a factor of 40 and fails bfloat16 by a factor of 100, and 2e-5 on
the loss passes with a factor of 40 and fails bfloat16 by 19 at the least;
``selftest.py`` holds the tolerance to both (every case must FAIL with
``bf16=True``).
"""

from __future__ import annotations

import glob
import json
import os
import sys

import numpy as np

from .references import load_reference, program_of

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKS = ("apply", "cache", "loss")
TOL = {"apply": 1e-4, "cache": 1e-4, "loss": 2e-5}
SEED = 26
SEQ = 96  # tokens of the apply and loss cases (two sequences)
PROMPTS = ((45, 64), (20, 32))  # (prompt length, the bucket it is padded to)


def cases() -> list:
    names = sorted(os.path.basename(p)[:-5]
                   for p in glob.glob(os.path.join(HERE, "configs", "*.json")))
    return [(name, check) for name in names for check in CHECKS]


def _seeded_params(tfm, cfg):
    import jax

    params = tfm.init(cfg, jax.random.PRNGKey(SEED))
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(SEED + 1), len(leaves))
    noisy = [x + 0.02 * jax.random.normal(k, x.shape, x.dtype) for x, k in zip(leaves, keys)]
    return jax.tree.unflatten(tree, noisy)


def error(config_name: str, check: str, *, bf16: bool = False) -> float:
    """max |system - reference| of one case. ``bf16``: the system computes in
    bfloat16 (what the tolerance must catch; never a case of its own)."""
    import jax.numpy as jnp

    from deepspeed_tpu.models import transformer as tfm

    from .drivers import serve

    with open(os.path.join(HERE, "configs", f"{config_name}.json")) as f:
        program = program_of(json.load(f), "rehearse_program")
    reference = load_reference(program)
    cfg = tfm.TransformerConfig(dtype=jnp.bfloat16 if bf16 else jnp.float32, **program)
    params = _seeded_params(tfm, cfg)
    rng = np.random.default_rng([SEED, CHECKS.index(check)])
    whole = lambda leaves: leaves
    if check == "apply":
        tokens = rng.integers(0, cfg.vocab_size, size=(2, SEQ)).astype(np.int32)
        got = np.asarray(tfm.apply(cfg, params, tokens), np.float32)
        want = np.stack([reference.logits_at(program, params, t, np.arange(SEQ), fetch=whole)
                         for t in tokens])
    elif check == "cache":
        prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32) for n, _ in PROMPTS]
        forced = rng.integers(0, cfg.vocab_size, size=(2, serve.DECODE_STEPS)).astype(np.int32)
        got = serve.probe_logits(cfg, params, prompts, [b for _, b in PROMPTS], forced)
        want = np.stack([
            reference.logits_at(program, params, np.concatenate([p, f]),
                                np.arange(len(p) - 1, len(p) + serve.DECODE_STEPS), fetch=whole)
            for p, f in zip(prompts, forced)])
    elif check == "loss":
        tokens = rng.integers(0, cfg.vocab_size, size=(2, SEQ + 1)).astype(np.int32)
        got = np.float32(tfm.causal_lm_loss(cfg, params, {"tokens": tokens}))
        want = np.float32(reference.lm_loss(program, params, tokens, fetch=whole))
    else:
        raise ValueError(f"no check {check!r}; there are: {', '.join(CHECKS)}")
    assert got.shape == want.shape, (got.shape, want.shape)
    assert np.isfinite(got).all() and np.isfinite(want).all(), "a non-finite value"
    return float(np.max(np.abs(got - want)))


def check(config_name: str, which: str) -> float:
    err = error(config_name, which)
    assert err <= TOL[which], (f"{config_name} {which}: the system is {err:.3g} from its "
                               f"reference (tolerance {TOL[which]})")
    return err


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    failed = 0
    for name, which in cases():
        try:
            print(f"ok    {name} {which}: {check(name, which):.3g} (tolerance {TOL[which]})")
        except AssertionError as e:
            failed += 1
            print(f"FAIL  {e}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
