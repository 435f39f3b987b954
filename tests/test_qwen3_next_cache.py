"""Qwen3-Next's delta twin through the cache (PR 52): the rule's block form against
its step form, every step against the reference, the state a delta layer keeps, slots
reused and idle rows, and how the forward-only layer loop reads the delta stack."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from qwen3_next_cases import (  # noqa: F401,I001 -- first: puts the repo's root on sys.path; fixtures
    WHOLE, TOL, program, reference, cfg, params, _tokens)

from chipbench.drivers import serve_delta  # noqa: E402
from deepspeed_tpu.models import transformer as tfm  # noqa: E402


STEPS = serve_delta.serve_latent.DECODE_STEPS
SAME = 2e-4  # the block form and the step form of ONE program, float32: summation order


def _bucket(n: int) -> int:
    return max(16, 1 << (n - 1).bit_length())


# prompts shorter than the filter's tail, as long as it, of one chunk and a row, of several chunks
@pytest.mark.parametrize("n", [1, 4, 65, 200])
def test_every_step_through_the_cache_matches_the_reference(cfg, params, program, reference, n):
    """The probe of the chip's check (bucket-padded prefill under the live-row mask
    into a local cache, ``update_cache_slot``, 8 decode steps at per-row positions):
    the state a delta layer hands the steps is that of the LIVE rows."""
    prompts = [_tokens(cfg, (n,), n), _tokens(cfg, (max(n - 3, 1),), n + 1)]
    forced = _tokens(cfg, (2, STEPS), n + 2)
    got, chosen = serve_delta.probe_logits(cfg, params, prompts, [_bucket(n)] * 2, forced)
    for j, (p, f) in enumerate(zip(prompts, forced)):
        rows = np.arange(len(p) - 1, len(p) + STEPS)
        ref = reference.routed_pass(program, params, np.concatenate([p, f]), rows, fetch=WHOLE,
                                    routing=chosen[j])
        assert np.max(np.abs(got[j] - ref["logits"])) <= TOL and ref["slack"] <= 1e-3


def _prefill(cfg, params, cache, slot, prompt, bucket=None):
    n, bucket = len(prompt), bucket or _bucket(len(prompt))
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n] = prompt
    local = tfm.init_cache(cfg, 1, bucket)
    logits, local = tfm.apply_with_cache(cfg, params, padded, local, 0, last_index=n - 1,
                                         live=jnp.arange(bucket)[None, :] < n)
    return np.asarray(logits[0, 0]), tfm.update_cache_slot(cache, local, slot), local


def _decode(cfg, params, cache, slot, start, tokens, n_rows=3):
    """Decode ``tokens`` at row ``slot`` from position ``start``, the other rows idle
    as ``SlotWorker`` rides them (position 0, their write dropped, not live) ->
    logits per step."""
    out = []
    for i, t in enumerate(tokens):
        toks = np.zeros((n_rows,), np.int32)
        pos = np.zeros((n_rows,), np.int32)
        wpos = np.full((n_rows,), tfm.cache_len(cache), np.int32)
        toks[slot], pos[slot], wpos[slot] = t, start + i, start + i
        logits, cache = tfm.apply_with_cache(
            cfg, params, toks[:, None], cache, jnp.asarray(pos), write_pos=jnp.asarray(wpos),
            live=jnp.asarray(np.arange(n_rows) == slot)[:, None])
        out.append(np.asarray(logits[slot, 0]))
    return np.stack(out), cache


def test_one_block_two_blocks_and_token_by_token_leave_the_same_state_tail_and_logits(cfg, params):
    """A 150-token prompt prefilled in ONE block, in TWO blocks (100 rows, then 50 from
    the carried state: the block form from a state given) and token by token (the
    recurrence): the same matrix state, the same filter tail, the same logits."""
    prompt = _tokens(cfg, (150,), 3)
    one = tfm.init_cache(cfg, 1, 256)
    whole, one = tfm.apply_with_cache(cfg, params, prompt[None], one, 0)
    two = tfm.init_cache(cfg, 1, 256)
    first, two = tfm.apply_with_cache(cfg, params, prompt[None, :100], two, 0)
    second, two = tfm.apply_with_cache(cfg, params, prompt[None, 100:], two, 100)
    steps = tfm.init_cache(cfg, 1, 256)
    step = jax.jit(lambda c, t, p: tfm.apply_with_cache(cfg, params, t, c, p, write_pos=p))
    by_token = []
    for i, t in enumerate(prompt):
        logits, steps = step(steps, jnp.full((1, 1), t, jnp.int32), jnp.full((1,), i, jnp.int32))
        by_token.append(np.asarray(logits[0, 0]))
    whole = np.asarray(whole[0])
    assert np.abs(np.concatenate([first[0], second[0]]) - whole).max() <= SAME
    assert np.abs(np.stack(by_token) - whole).max() <= SAME
    for other in (two, steps):
        for leaf in ("delta", "conv"):
            a, b = np.asarray(one[tfm.STATE][leaf]), np.asarray(other[tfm.STATE][leaf])
            assert np.abs(a).max() > 1e-2 and np.abs(a - b).max() <= SAME, leaf
        np.testing.assert_allclose(np.asarray(other["k"])[:, :, :150], np.asarray(one["k"])[:, :, :150],
                                   atol=SAME)


@pytest.mark.parametrize("n", [1, 2, 5, 70])
def test_a_padded_block_leaves_the_state_of_its_live_rows_bit_for_bit(cfg, params, n):
    """A prompt of ``n`` rows padded to its bucket: on the padding g = 0 and beta = 0,
    so the matrix passes through EXACTLY, and the tail kept is that of the last three
    LIVE rows (zero rows in front where the prompt is shorter); padding drawn from
    other tokens changes not a bit of either."""
    prompt, bucket = _tokens(cfg, (n,), n), _bucket(n + 10)
    _, _, padded = _prefill(cfg, params, tfm.init_cache(cfg, 1, 128), 0, prompt, bucket=bucket)
    noisy = np.full((1, bucket), 7, np.int32)
    noisy[0, :n] = prompt
    local = tfm.init_cache(cfg, 1, bucket)
    _, local = tfm.apply_with_cache(cfg, params, noisy, local, 0, last_index=n - 1,
                                    live=jnp.arange(bucket)[None, :] < n)
    for leaf in ("delta", "conv"):
        np.testing.assert_array_equal(np.asarray(local[tfm.STATE][leaf]),
                                      np.asarray(padded[tfm.STATE][leaf]))
    exact = tfm.init_cache(cfg, 1, n)
    _, exact = tfm.apply_with_cache(cfg, params, prompt[None], exact, 0)
    tail = np.asarray(padded[tfm.STATE]["conv"])
    assert tail.shape == (6, 1, 3, 128)
    np.testing.assert_allclose(tail, np.asarray(exact[tfm.STATE]["conv"]), atol=2e-5)
    np.testing.assert_allclose(np.asarray(padded[tfm.STATE]["delta"]),
                               np.asarray(exact[tfm.STATE]["delta"]), atol=2e-5)
    assert np.abs(tail[:, 0, -1]).min() > 0  # the last live row's input
    assert (n >= 3) == bool(np.abs(tail[:, 0, 0]).max() > 0)  # before the start: zero


def test_a_slot_reused_by_a_shorter_request_and_idle_rows_riding_the_steps(cfg, params):
    """A 90-token request, then a 1-token one in the same slot, while another slot
    holds a prefilled sequence that only rides along (idle: not live): every logit of
    the second request is ``apply``'s of it alone, the riding slot's state is
    untouched BIT FOR BIT by the thirteen steps, and its own steps afterwards are
    ``apply``'s."""
    long, short, other = _tokens(cfg, (90,), 1), _tokens(cfg, (12,), 2), _tokens(cfg, (30,), 3)
    cache = tfm.init_cache(cfg, 3, 128)
    _, cache, _ = _prefill(cfg, params, cache, 1, long)
    _, cache, _ = _prefill(cfg, params, cache, 2, other[:20])
    parked = {k: np.asarray(v)[:, 2].copy() for k, v in cache[tfm.STATE].items()}
    _, cache = _decode(cfg, params, cache, 1, 90, _tokens(cfg, (5,), 4))
    first, cache, _ = _prefill(cfg, params, cache, 1, short[:1])  # a prompt of ONE row
    steps, cache = _decode(cfg, params, cache, 1, 1, short[1:9])  # eight steps
    want = np.asarray(tfm.apply(cfg, params, short[None]))[0]
    assert np.max(np.abs(first - want[0])) <= SAME
    assert np.max(np.abs(steps - want[1:9])) <= SAME
    for leaf, was in parked.items():  # rode thirteen steps: moved by none
        np.testing.assert_array_equal(np.asarray(cache[tfm.STATE][leaf])[:, 2], was)
        assert not np.asarray(cache[tfm.STATE][leaf])[:, 0].any()  # never used
    rest, cache = _decode(cfg, params, cache, 2, 20, other[20:28])
    want = np.asarray(tfm.apply(cfg, params, other[None]))[0]
    assert np.max(np.abs(rest - want[20:28])) <= SAME


def test_a_row_not_marked_idle_moves_its_state(cfg, params):
    """The control of the test above: without ``live`` the riding row's state moves."""
    cache = tfm.init_cache(cfg, 2, 64)
    _, cache, _ = _prefill(cfg, params, cache, 1, _tokens(cfg, (20,), 3))
    parked = np.asarray(cache[tfm.STATE]["delta"])[:, 1].copy()
    pos = jnp.asarray([0, 0])
    _, cache = tfm.apply_with_cache(cfg, params, np.asarray([[5], [9]], np.int32), cache, pos,
                                    write_pos=jnp.asarray([0, 64]))
    assert np.abs(np.asarray(cache[tfm.STATE]["delta"])[:, 1] - parked).max() > 1e-3


def test_a_padded_block_without_its_live_rows_is_refused(cfg, params):
    with pytest.raises(ValueError, match="live"):
        tfm.apply_with_cache(cfg, params, np.zeros((1, 16), np.int32), tfm.init_cache(cfg, 1, 16),
                             0, last_index=3)


def test_the_inverse_of_a_chunk_is_exact_and_loops_over_no_row():
    """(I + A)^-1 by the six factors of a nilpotent A against a triangular solve, and
    the compiled block form holds no loop but the one over chunks."""
    A = jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (3, 64, 64)), -1) * 0.3
    inv = tfm._unit_lower_inverse(A)
    want = jax.scipy.linalg.solve_triangular(jnp.eye(64) + A, jnp.broadcast_to(jnp.eye(64), A.shape),
                                             lower=True, unit_diagonal=True)
    assert float(jnp.abs(inv - want).max() / jnp.abs(want).max()) <= 1e-5
    k = jax.random.PRNGKey(1)
    args = (jax.random.normal(k, (1, 200, 2, 16)), jax.random.normal(k, (1, 200, 2, 16)),
            jax.random.normal(k, (1, 200, 4, 16)), -jnp.ones((1, 200, 4)), jnp.ones((1, 200, 4)) / 2,
            jnp.zeros((1, 4, 16, 16)))
    text = str(jax.make_jaxpr(tfm._delta_chunks)(*args))
    assert text.count(" scan[") + text.count(" while[") == 1  # the loop over chunks, no other
    assert "length=4" in text  # ceil(200 / 64) chunks


def test_the_forward_only_loop_reads_the_delta_stack_where_it_lies(cfg, params):
    """The cache path (forward only) hands its scanned periods the layers' indices
    and reads the held stacks at them; ``apply`` (a backward pass may follow) scans
    the periods' slices. Same logits."""
    def stacks_scanned(fn, *args):
        text = str(jax.make_jaxpr(fn)(*args))
        return text.count("f32[2,3,64,192]")  # the periods' share of delta_in: [G, n, ...]

    tokens = _tokens(cfg, (1, 24))
    cache = tfm.init_cache(cfg, 1, 24)
    assert stacks_scanned(lambda p: tfm.apply(cfg, p, tokens), params) > 0
    assert stacks_scanned(lambda p: tfm.apply_with_cache(cfg, p, tokens, cache, 0)[0], params) == 0
    got = tfm.apply_with_cache(cfg, params, tokens, cache, 0)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(tfm.apply(cfg, params, tokens)),
                               atol=SAME)
