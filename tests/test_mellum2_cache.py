"""Mellum2-12B-A2.5B through the cache: a prompt that enters the two-kind cache IN CHUNKS
(a block into a window layer's ring past position 0 attends over [ring ; block] before the
ring is written; a whole-context layer walks the key blocks before it) is the prompt
prefilled whole is ``apply`` is the plain reference, for chunk widths below, equal to and
above the window, boundaries off the window's multiples, prompts that wrap the ring many
times and padded tails; each planted fault is seen; rows of K/V heads beside rings of
heads are the same."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from mellum2_cases import (PLANTED, TOL, WHOLE, WINDOW, _tokens, cfg, chunked, params,  # noqa: F401
                           planted, program, reference, segments, whole_prompt)

from chipbench import parity
from deepspeed_tpu.models import transformer as tfm

STEPS = 6
SMAX = 256


@pytest.fixture(scope="module")
def prompt(cfg):
    return _tokens(cfg, (150,), seed=11)  # wraps the ring of 16 nine times


@pytest.fixture(scope="module")
def steps(cfg):
    return _tokens(cfg, (STEPS,), seed=12)


@pytest.fixture(scope="module")
def want(prompt, steps, program, params, reference):
    seq = np.concatenate([prompt, steps])
    return reference.logits_at(program, params, seq, np.arange(len(seq) - 1), fetch=WHOLE)


def test_segments_are_the_engines(cfg):
    assert segments(150, 64) == [(0, 64, 64), (64, 64, 64), (128, 32, 22)]
    assert segments(13, 64) == [(0, 16, 13)] and segments(128, 64) == [(0, 64, 64), (64, 64, 64)]


@pytest.mark.parametrize("chunk", [8, 16, 32, 64])  # below, equal to and above the window
def test_chunked_prefill_and_decode_is_the_reference(cfg, params, prompt, steps, want, chunk):
    """Every live row's logits of every chunk, and of the decode steps behind them through
    the rings the chunks left."""
    assert WINDOW == cfg.local_attn_window
    got, _ = chunked(cfg, params, prompt, chunk, SMAX, steps[:-1])
    assert got.shape == want.shape and np.max(np.abs(got - want)) <= TOL


@pytest.mark.parametrize("n,chunk", [(150, 8), (77, 32), (40, 64), (129, 64)])
def test_chunked_is_whole_prompt_prefill(cfg, params, steps, n, chunk):
    """Boundaries off the window's multiples (150 = 18 x 8 + a tail of 6 in a bucket of 8),
    a lone padded tail that wraps the ring (40 in a chunk of 64), one live row behind two
    whole chunks (129): the same logits, and the same RINGS entry for entry."""
    p = _tokens(cfg, (n,), seed=n)
    a, cache_a = chunked(cfg, params, p, chunk, SMAX, steps)
    b, cache_b = whole_prompt(cfg, params, p, SMAX, steps)
    assert np.max(np.abs(a - b)) <= TOL
    for name in ("k", "v"):
        ra, rb = np.asarray(cache_a[tfm.RING][name]), np.asarray(cache_b[tfm.RING][name])
        live = min(n + STEPS, WINDOW)
        assert ra.shape == rb.shape == (6, 1, WINDOW, 2, 24)
        assert np.max(np.abs(ra - rb)) <= 1e-5 if live == WINDOW else True


def test_rows_beside_rings_are_the_reference(program, reference, prompt, steps):
    """The twin at heads of 64 (two K/V heads side by side are one row of 128): its two
    whole-context layers' K and V lie as ROWS beside rings that keep their heads, as the
    cell's do at the published widths (``cache_heads_merged``: a decode step contracts a
    layer where it lies in the stack, a chunk views its slot's rows as heads), and chunks,
    a whole-prompt prefill and the decode steps behind either are the reference's rows."""
    wide = {**program, "qk_head_dim": 64}
    cfg = tfm.TransformerConfig(dtype=jnp.float32, **wide)
    assert tfm.cache_heads_merged(cfg) and tfm.cache_rows_step(cfg)
    assert tfm.cache_layout(cfg) == {"k": (1, 128), "v": (1, 128),
                                     tfm.RING: {"k": (WINDOW, 2, 64), "v": (WINDOW, 2, 64)}}
    one_whole = cfg.replace(num_layers=4, local_attn_layers=(1, 1, 1, 0))
    assert not tfm.cache_heads_merged(one_whole)  # ONE whole-context layer is read in place
    params = parity._seeded_params(tfm, cfg)
    seq = np.concatenate([prompt, steps])
    want = reference.logits_at(wide, params, seq, np.arange(len(seq) - 1), fetch=WHOLE)
    got, cache = chunked(cfg, params, prompt, 32, SMAX, steps[:-1])
    assert cache["k"].shape == (2, 1, SMAX, 1, 128)
    assert got.shape == want.shape and np.max(np.abs(got - want)) <= TOL
    whole, _ = whole_prompt(cfg, params, prompt, SMAX, steps[:-1])
    assert whole.shape == want.shape and np.max(np.abs(whole - want)) <= TOL


def test_a_chunk_enters_a_ring_where_the_parent_refused(cfg, params):
    """A block of 8 at position 20 (what ``_cache_attention`` refused by name until PR 59)
    behind a prefill of 20: the rows of ``apply``."""
    tokens = _tokens(cfg, (1, 28), seed=2)
    full = np.asarray(tfm.apply(cfg, params, tokens))[0]
    _, cache = tfm.apply_with_cache(cfg, params, tokens[:, :20], tfm.init_cache(cfg, 1, 64), 0)
    logits, _ = tfm.apply_with_cache(cfg, params, tokens[:, 20:], cache, jnp.asarray([20]))
    assert np.max(np.abs(np.asarray(logits)[0] - full[20:])) <= TOL


def test_rows_of_a_batch_enter_at_their_own_positions(cfg, params):
    """Two sequences, one chunk each at positions 24 and 7 of their own: each row's ring is
    ordered from ITS base (position 8, and position 0 where the sequence is shorter than
    the window)."""
    tokens = _tokens(cfg, (2, 40), seed=4)
    full = np.asarray(tfm.apply(cfg, params, tokens))
    cache = tfm.init_cache(cfg, 2, 64)
    for j, at in enumerate((24, 7)):
        _, local = tfm.apply_with_cache(cfg, params, tokens[j:j + 1, :at],
                                        tfm.init_cache(cfg, 1, 64), 0)
        cache = tfm.update_cache_slot(cache, tfm.slice_cache_slot(local, 0, 64), j)
    block = np.stack([tokens[0, 24:36], tokens[1, 7:19]])
    logits, _ = tfm.apply_with_cache(cfg, params, block, cache, jnp.asarray([24, 7]))
    assert np.max(np.abs(np.asarray(logits)[0] - full[0, 24:36])) <= TOL
    assert np.max(np.abs(np.asarray(logits)[1] - full[1, 7:19])) <= TOL


@pytest.mark.parametrize("fault", list(PLANTED))
def test_a_planted_fault_is_seen(cfg, params, prompt, steps, want, fault):
    with planted(fault):
        got, _ = chunked(cfg, params, prompt, 32, SMAX, steps[:-1])
    assert np.max(np.abs(got - want)) > 10 * TOL, fault


def test_a_verify_block_into_a_ring_is_still_refused(cfg, params):
    block = _tokens(cfg, (2, 4))
    pos = jnp.asarray([20, 9])
    with pytest.raises(NotImplementedError, match="rolled back"):
        tfm.apply_with_cache(cfg, params, block, tfm.init_cache(cfg, 2, 64), pos, write_pos=pos)


@pytest.mark.parametrize("band", ["dense", "flash"])
def test_long_blocks_walk_the_live_key_blocks_alone(cfg, params, prompt, steps, want,
                                                    monkeypatch, band):
    """Over ``DENSE_SCORE_BYTES`` a chunk's whole-context layers walk the key blocks up to
    the newest position (``_blocks_attention``: the one walk, here as on the chip), not
    ``Smax`` densely, with the window layers' [ring ; chunk] densely or through the flash
    forward's band (interpreted): the same logits."""
    monkeypatch.setattr(tfm, "DENSE_SCORE_BYTES", 1)
    monkeypatch.setattr(tfm, "CHUNK_KEY_BLOCK", 32)
    assert tfm.cache_chunk_form(cfg, 1, 32, SMAX) == "blocks"
    assert tfm.cache_chunk_form(cfg, 1, 1, SMAX) == "dense"  # a step is never a walk
    assert tfm.cache_chunk_form(cfg, 1, 32, SMAX + 8) == "dense"  # no key block divides it
    if band == "dense":  # the window layers densely: a threshold between the two
        monkeypatch.setattr(tfm, "cache_attention_form", lambda *a, **k: "dense")
    got, _ = chunked(cfg, params, prompt, 32, SMAX, steps[:-1])
    assert np.max(np.abs(got - want)) <= TOL
