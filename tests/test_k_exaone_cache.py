"""K-EXAONE-236B-A23B's twin through the cache (split from ``test_k_exaone.py``, PR 47):
every step through rings and whole-context layers against the reference, a slot
reused, GPT-Neo's local layers through the same rings, and what a ring cannot carry."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k_exaone_cases import (  # noqa: F401,I001 -- first: puts the repo's root on sys.path; fixtures
    WHOLE, TOL, program, reference, cfg, params, _tokens, _bucket, _prefill)

from chipbench import parity  # noqa: E402
from chipbench.drivers import serve_kinds  # noqa: E402
from deepspeed_tpu.models import transformer as tfm  # noqa: E402


# shorter than the window, the window exactly, one more, several windows; each padded to a bucket
@pytest.mark.parametrize("n", [5, 16, 17, 50, 200])
def test_every_step_through_the_kinds_cache_matches_the_reference(cfg, params, program,
                                                                   reference, n):
    """The probe of the chip's check (bucket-padded prefill under the live-row
    mask into a local cache, ``update_cache_slot``, 8 decode steps at per-row
    positions): the ring holds the last 16 LIVE rows, not the bucket's last, and
    the steps wrap it."""
    prompts = [_tokens(cfg, (n,), n), _tokens(cfg, (max(n - 3, 1),), n + 1)]
    forced = _tokens(cfg, (2, serve_kinds.DECODE_STEPS), n + 2)
    got, chosen = serve_kinds.probe_logits(cfg, params, prompts, [_bucket(n)] * 2, forced)
    for j, (p, f) in enumerate(zip(prompts, forced)):
        rows = np.arange(len(p) - 1, len(p) + serve_kinds.DECODE_STEPS)
        ref = reference.routed_pass(program, params, np.concatenate([p, f]), rows, fetch=WHOLE,
                                    routing=chosen[j])
        assert np.max(np.abs(got[j] - ref["logits"])) <= TOL and ref["slack"] <= 1e-4


def _decode(cfg, params, cache, slot, start, tokens, n_rows=3):
    """Greedy-free decode of ``tokens`` at row ``slot`` from position ``start``,
    the other rows idle (position 0, their write dropped) -> logits per step."""
    out = []
    for i, t in enumerate(tokens):
        toks = np.zeros((n_rows,), np.int32)
        pos = np.zeros((n_rows,), np.int32)
        wpos = np.full((n_rows,), tfm.cache_len(cache), np.int32)
        toks[slot], pos[slot], wpos[slot] = t, start + i, start + i
        logits, cache = tfm.apply_with_cache(cfg, params, toks[:, None], cache,
                                             jnp.asarray(pos), write_pos=jnp.asarray(wpos))
        out.append(np.asarray(logits[slot, 0]))
    return np.stack(out), cache


def test_a_slot_reused_by_a_shorter_request_reads_nothing_of_the_last(cfg, params):
    """A 90-token request, then a 7-token one in the same slot, idle rows riding
    along: every logit is ``apply``'s of the second sequence alone (the ring's
    stale entries hold positions the mask counts as never written)."""
    long, short = _tokens(cfg, (90,), 1), _tokens(cfg, (40,), 2)
    cache = tfm.init_cache(cfg, 3, 128)
    _, cache = _prefill(cfg, params, cache, 1, long)
    _, cache = _decode(cfg, params, cache, 1, 90, _tokens(cfg, (5,), 3))
    first, cache = _prefill(cfg, params, cache, 1, short[:7])
    steps, cache = _decode(cfg, params, cache, 1, 7, short[7:])  # past two wraps of the ring
    want = np.asarray(tfm.apply(cfg, params, short[None]))[0]
    assert np.max(np.abs(first - want[6])) <= TOL
    assert np.max(np.abs(steps - want[7:])) <= TOL
    # the idle rows' writes were dropped: their rings are as they were made
    assert not np.asarray(cache[tfm.RING]["k"])[:, [0, 2]].any()


# -- GPT-Neo's local layers are the same thing -----------------------------------------------------


@pytest.mark.parametrize("decode_attn", ["xla", "kernel"])
def test_gpt_neo_local_layers_are_served_through_the_same_rings(decode_attn):
    """GPT-Neo's alternating local attention (learned positions, multi-head, a
    LayerNorm block) through ``apply_with_cache``, which refused it before this
    PR: a ring for the local layers, ``Smax`` (and, where asked, the Pallas decode
    kernel) for the global ones; every logit is ``apply``'s."""
    cfg = tfm.TransformerConfig(vocab_size=211, max_seq_len=128, num_layers=4, num_heads=4,
                                hidden_size=64, local_attn_window=8,
                                local_attn_layers=(0, 1, 0, 1), decode_attn=decode_attn)
    params = parity._seeded_params(tfm, cfg)
    tokens = _tokens(cfg, (45,), 8)
    want = np.asarray(tfm.apply(cfg, params, tokens[None]))[0]
    cache = tfm.init_cache(cfg, 3, 128)
    assert cache["k"].shape[0] == 2 and cache[tfm.RING]["k"].shape[:3] == (2, 3, 8)
    first, cache = _prefill(cfg, params, cache, 1, tokens[:21])
    steps, _ = _decode(cfg, params, cache, 1, 21, tokens[21:])
    assert np.max(np.abs(first - want[20])) <= 2e-5 and np.max(np.abs(steps - want[21:])) <= 2e-5


def test_the_cache_path_refuses_what_a_ring_cannot_carry(cfg, params):
    cache = tfm.init_cache(cfg, 1, 64)
    block = _tokens(cfg, (1, 8))
    # a chunk entering past position 0 has code since PR 59 (``tests/test_mellum2_cache.py``
    # holds it to the reference); a verify block, written apart from where it attends, has none
    logits, _ = tfm.apply_with_cache(cfg, params, block, cache, jnp.asarray([20]))
    assert logits.shape == (1, 8, cfg.vocab_size) and bool(jnp.isfinite(logits).all())
    with pytest.raises(NotImplementedError, match="rolled back"):
        tfm.apply_with_cache(cfg, params, block, cache, jnp.asarray([20]),
                             write_pos=jnp.asarray([20]))
    with pytest.raises(ValueError, match="live"):  # a padded block with no live-row mask
        tfm.apply_with_cache(cfg, params, block, tfm.init_cache(cfg, 1, 8), 0, last_index=4)
    alibi = tfm.TransformerConfig(vocab_size=64, max_seq_len=64, num_layers=2, num_heads=2,
                                  hidden_size=32, pos_emb="alibi", local_attn_window=4,
                                  local_attn_layers=(1, 0), decode_attn="xla")
    with pytest.raises(NotImplementedError, match="alibi"):
        tfm.apply_with_cache(alibi, tfm.init(alibi, jax.random.PRNGKey(0)),
                             np.zeros((1, 4), np.int32), tfm.init_cache(alibi, 1, 4), 0)
