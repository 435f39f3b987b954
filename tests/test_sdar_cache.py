"""SDAR-30B-A3B-Chat's twin through the slot cache: a bucket-padded prefill of the prompt's
whole blocks, then every pass of three blocks as block steps over [slots, B] rows at
per-row positions, against the reference's whole forward pass of the sequence as it stood
at that pass, fed the reference's own reveals (teacher forced); the rows the system would
reveal wherever the reference's confidences are apart; and the planted faults, each of
which must fail."""

import numpy as np
import pytest

from sdar_cases import (  # noqa: F401,I001 -- first: puts the repo's root on sys.path; fixtures
    ROOT, WHOLE, TOL, BLOCK, program, reference, cfg, params, _tokens, through_cache,
    causal_inside_a_block, _config)

from chipbench import parity  # noqa: E402
from deepspeed_tpu.models import transformer as tfm  # noqa: E402

BLOCKS = 3  # generated blocks a case takes through the cache


def _passes(program, reference, params, prompt, steps=4):
    """The reference's own run: every pass of ``BLOCKS`` blocks, commits with their logits."""
    n = BLOCKS * BLOCK - (len(prompt) % BLOCK or BLOCK) + BLOCK  # ends inside the last block
    out = reference.generate(program, params, prompt, n, fetch=WHOLE, denoising_steps=steps,
                             commit_logits=True)
    starts = sorted({p["start"] for p in out["passes"]})[:BLOCKS]
    return [p for p in out["passes"] if p["start"] in starts]


def _worst(passes, got):
    return max(float(np.abs(g - p["logits"]).max()) for p, g in zip(passes, got) if g is not None)


@pytest.mark.parametrize("plen", [40, 41, 43, 3], ids=["r0", "r1", "r3", "under_a_block"])
def test_every_pass_of_three_blocks_is_the_references_forward(program, reference, cfg, params,
                                                              plen):
    prompt = _tokens(cfg, (plen,), plen)
    passes = _passes(program, reference, params, prompt)
    assert sum(p["commit"] for p in passes) == BLOCKS
    got = through_cache(cfg, params, passes, prompt)
    assert _worst(passes, got) <= TOL
    # the rows the system would reveal are the reference's wherever its confidences are apart
    for p, g in zip(passes, got):
        if p["commit"]:
            continue
        z = g.astype(np.float64)
        conf = np.exp(z.max(axis=-1) - np.log(np.exp(z - z.max(axis=-1, keepdims=True)).sum(-1))
                      - z.max(axis=-1))
        rows = [q - p["start"] for q in p["masked"]]
        ranked = sorted(rows, key=lambda r: -p["confidence"][r])
        apart = len(ranked) < 2 or all(
            p["confidence"][a] - p["confidence"][b] > 1e-4 * p["confidence"][a]
            for a, b in zip(ranked, ranked[1:]))
        if apart:
            mine = sorted(rows, key=lambda r: -conf[r])[:len(p["revealed"])]
            assert sorted(mine) == sorted(q - p["start"] for q in p["revealed"])
            assert all(int(np.argmax(g[r])) == int(p["x0"][r]) for r in mine)


@pytest.mark.parametrize("steps", [1, 2])
def test_other_schedules_go_through_the_same_step(program, reference, cfg, params, steps):
    prompt = _tokens(cfg, (22,), 22)
    passes = _passes(program, reference, params, prompt, steps)
    assert _worst(passes, through_cache(cfg, params, passes, prompt)) <= TOL


def test_bfloat16_compute_fails_the_tolerance(program, reference, params):
    import jax.numpy as jnp

    cfg16 = tfm.TransformerConfig(dtype=jnp.bfloat16, **program)
    prompt = _tokens(cfg16, (41,), 41)
    passes = _passes(program, reference, params, prompt)
    assert _worst(passes, through_cache(cfg16, params, passes, prompt)) > 10 * TOL


@pytest.mark.parametrize("fault", ["commit_skipped", "written_one_off", "causal_inside_a_block",
                                   "causal_prefill_only"])
def test_every_planted_fault_fails_the_tolerance(program, reference, cfg, params, fault,
                                                 monkeypatch):
    """What the cell's check must catch (``drivers/serve_blocks.py``), at the tiny size:
    the commit skipped (the next block reads K/V computed from a block that still held a
    mask), a block's K/V written one position off, the causal mask inside a block (in the
    steps and the prefill, and in the prefill's flash form alone)."""
    prompt = _tokens(cfg, (43,), 9)
    passes = _passes(program, reference, params, prompt)
    kw = {"commit_skipped": dict(skip_commit=True), "written_one_off": dict(write_off=1)}
    if fault == "causal_inside_a_block":
        with causal_inside_a_block():
            got = through_cache(cfg, params, passes, prompt)
    elif fault == "causal_prefill_only":
        # the flash kernel with the causal compare: only the prompt's K/V is wrong
        from deepspeed_tpu.ops.pallas import flash_attention as fa

        monkeypatch.setattr(tfm, "DENSE_SCORE_BYTES", 0)
        real = fa._block_scores
        monkeypatch.setattr(fa, "_block_scores",
                            lambda *a, **k: real(*a, **{**k, "mask_block": 1}))
        got = through_cache(cfg, params, passes, prompt)
    else:
        got = through_cache(cfg, params, passes, prompt, **kw[fault])
    assert _worst(passes, got) > 100 * TOL


def test_parity_takes_the_causal_twin_with_no_edit():
    """``rehearse_program`` has no block key: ``chipbench/parity.py``'s three surfaces are
    cases of it like any configuration's (``tests/test_reference_parity.py`` runs them)."""
    assert {("sdar-30b-a3b-L7", c) for c in parity.CHECKS} <= set(parity.cases())
    assert "attn_block_length" not in _config()["rehearse_program"]
