"""LFM2-24B-A2B's twin through the cache (split from ``test_lfm2.py``, PR 47): every step
against the reference, the state a conv layer keeps, slots reused and idle rows, and how
the forward-only layer loop reads the stacks by operator and the expert banks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lfm2_cases import (  # noqa: F401,I001 -- first: puts the repo's root on sys.path; fixtures
    WHOLE, TOL, program, reference, cfg, params, _tokens)

from chipbench.drivers import serve_shortconv  # noqa: E402
from deepspeed_tpu.models import transformer as tfm  # noqa: E402
from deepspeed_tpu.moe import dropless  # noqa: E402


STEPS = serve_shortconv.serve_latent.DECODE_STEPS


def _bucket(n: int) -> int:
    return max(16, 1 << (n - 1).bit_length())


# prompts of 1, 2 and 3 rows (fewer than, as many as, one more than the state's rows), a
# bucket's worth, and past one; each padded to its bucket (the second prompt 3 shorter)
@pytest.mark.parametrize("n", [1, 2, 3, 4, 16, 17, 50, 200])
def test_every_step_through_the_two_kind_cache_matches_the_reference(cfg, params, program,
                                                                      reference, n):
    """The probe of the chip's check (bucket-padded prefill under the live-row mask
    into a local cache, ``update_cache_slot``, 8 decode steps at per-row
    positions): the state a conv layer hands the steps is that of the last two
    LIVE rows, not the bucket's last two."""
    prompts = [_tokens(cfg, (n,), n), _tokens(cfg, (max(n - 3, 1),), n + 1)]
    forced = _tokens(cfg, (2, STEPS), n + 2)
    got, chosen = serve_shortconv.probe_logits(cfg, params, prompts, [_bucket(n)] * 2, forced)
    for j, (p, f) in enumerate(zip(prompts, forced)):
        rows = np.arange(len(p) - 1, len(p) + STEPS)
        ref = reference.routed_pass(program, params, np.concatenate([p, f]), rows, fetch=WHOLE,
                                    routing=chosen[j])
        assert np.max(np.abs(got[j] - ref["logits"])) <= TOL and ref["slack"] <= 1e-4


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


def _prefill(cfg, params, cache, slot, prompt, bucket=None):
    n, bucket = len(prompt), bucket or _bucket(len(prompt))
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n] = prompt
    local = tfm.init_cache(cfg, 1, bucket)
    logits, local = tfm.apply_with_cache(cfg, params, padded, local, 0, last_index=n - 1,
                                         live=jnp.arange(bucket)[None, :] < n)
    return np.asarray(logits[0, 0]), tfm.update_cache_slot(cache, local, slot), local


@pytest.mark.parametrize("n", [1, 2, 3, 9])
def test_the_state_is_the_last_two_live_rows_of_the_filters_input(cfg, params, n):
    """A prompt of ``n`` rows padded to 16 leaves each conv layer the state an
    unpadded block of exactly ``n`` rows leaves (zero rows in front where the
    prompt is shorter than two), and padding drawn from other tokens changes
    nothing of it."""
    prompt = _tokens(cfg, (n,), n)
    _, _, padded = _prefill(cfg, params, tfm.init_cache(cfg, 1, 32), 0, prompt, bucket=16)
    exact = tfm.init_cache(cfg, 1, n)
    _, exact = tfm.apply_with_cache(cfg, params, prompt[None], exact, 0)
    state = np.asarray(padded[tfm.STATE]["conv"])
    assert state.shape == (5, 1, 2, 64)
    np.testing.assert_allclose(state, np.asarray(exact[tfm.STATE]["conv"]), atol=2e-5)
    assert np.abs(state[:, 0, -1]).min() > 0  # the last live row's input
    assert (n >= 2) == bool(np.abs(state[:, 0, 0]).max() > 0)  # before the start: zero
    noisy = np.full((1, 16), 7, np.int32)
    noisy[0, :n] = prompt
    local = tfm.init_cache(cfg, 1, 16)
    _, local = tfm.apply_with_cache(cfg, params, noisy, local, 0, last_index=n - 1,
                                    live=jnp.arange(16)[None, :] < n)
    np.testing.assert_array_equal(np.asarray(local[tfm.STATE]["conv"]), state)


def test_a_slot_reused_by_a_shorter_request_and_idle_rows_riding_the_steps(cfg, params):
    """A 90-token request, then a 1-token one in the same slot, while another slot
    holds a prefilled sequence that only rides along (idle: not live): every logit
    of the second request is ``apply``'s of it alone, the riding slot's state is
    untouched by the eight steps, and its own steps afterwards are ``apply``'s."""
    long, short, other = _tokens(cfg, (90,), 1), _tokens(cfg, (12,), 2), _tokens(cfg, (30,), 3)
    cache = tfm.init_cache(cfg, 3, 128)
    _, cache, _ = _prefill(cfg, params, cache, 1, long)
    _, cache, _ = _prefill(cfg, params, cache, 2, other[:20])
    parked = np.asarray(cache[tfm.STATE]["conv"])[:, 2].copy()
    _, cache = _decode(cfg, params, cache, 1, 90, _tokens(cfg, (5,), 4))
    first, cache, _ = _prefill(cfg, params, cache, 1, short[:1])  # a prompt of ONE row
    steps, cache = _decode(cfg, params, cache, 1, 1, short[1:9])  # eight steps
    want = np.asarray(tfm.apply(cfg, params, short[None]))[0]
    assert np.max(np.abs(first - want[0])) <= TOL
    assert np.max(np.abs(steps - want[1:9])) <= TOL
    state = np.asarray(cache[tfm.STATE]["conv"])
    np.testing.assert_array_equal(state[:, 2], parked)  # rode thirteen steps: moved by none
    assert not state[:, 0].any() and not np.asarray(cache["k"])[:, 0].any()  # never used
    rest, cache = _decode(cfg, params, cache, 2, 20, other[20:28])
    want = np.asarray(tfm.apply(cfg, params, other[None]))[0]
    assert np.max(np.abs(rest - want[20:28])) <= TOL


def test_a_row_not_marked_idle_moves_its_state(cfg, params):
    """The control of the test above: without ``live`` the riding row's state moves."""
    cache = tfm.init_cache(cfg, 2, 64)
    _, cache, _ = _prefill(cfg, params, cache, 1, _tokens(cfg, (20,), 3))
    parked = np.asarray(cache[tfm.STATE]["conv"])[:, 1].copy()
    pos = jnp.asarray([0, 0])
    _, cache = tfm.apply_with_cache(cfg, params, np.asarray([[5], [9]], np.int32), cache, pos,
                                    write_pos=jnp.asarray([0, 64]))
    assert np.abs(np.asarray(cache[tfm.STATE]["conv"])[:, 1] - parked).max() > 1e-3


# -- the lead shifts the routed position (PR 34's fault, planted) ----------------------------------


@pytest.mark.parametrize("rows", [48, 520], ids=["dense_form", "sorted_form"])
def test_the_stack_index_is_the_routed_layers_not_the_models(cfg, params, rows, monkeypatch):
    """Behind ONE leading dense layer the model's layer 1 is routed stack 0, inline
    and in the scanned periods alike: the in-place programs (one chip) give the
    sliced programs' logits, and with the model's layer number in the index's
    place they do not."""
    tokens = _tokens(cfg, (1, rows), rows)

    def run():
        cache = tfm.init_cache(cfg, 1, rows)
        return tfm.apply_with_cache(cfg, params, tokens, cache, 0, last_only=True)[0]

    sliced = run()
    seen = []
    real = dropless.moe_ffn_dropless

    def spy(c, p, h, layer=None):
        seen.append(layer is not None)
        return real(c, p, h, layer)

    monkeypatch.setattr(tfm, "_ACTIVE_MESH", [None])  # one chip: the banks read in place
    monkeypatch.setattr(tfm, "expert_bank_form", lambda *a, **k: "in_place")
    with monkeypatch.context() as m:
        m.setattr(dropless, "moe_ffn_dropless", spy)
        in_place = run()
    assert seen and all(seen)
    np.testing.assert_allclose(np.asarray(in_place), np.asarray(sliced), atol=2e-5)
    with monkeypatch.context() as m:
        m.setattr(dropless, "moe_ffn_dropless",
                  lambda c, p, h, layer=None: real(c, p, h, None if layer is None
                                                   else jnp.minimum(layer + 1, 5)))
        off_by_the_lead = run()
    assert float(jnp.max(jnp.abs(off_by_the_lead - sliced))) > 1e-2


def test_the_forward_only_loop_reads_the_operator_stacks_where_they_lie(cfg, params):
    """The cache path (forward only) hands its scanned periods the layers' indices
    and reads the held stacks at them; ``apply`` (a backward pass may follow) scans
    the periods' slices. Same logits."""
    def stacks_scanned(fn, *args):
        text = str(jax.make_jaxpr(fn)(*args))
        scans = [line for line in text.splitlines() if " scan[" in line]
        return text.count("f32[1,3,64,192]"), len(scans)  # a period's share of conv_in: [G, n, ...]

    tokens = _tokens(cfg, (1, 24))
    fwd_bwd = stacks_scanned(lambda p: tfm.apply(cfg, p, tokens), params)
    cache = tfm.init_cache(cfg, 1, 24)
    fwd = stacks_scanned(lambda p: tfm.apply_with_cache(cfg, p, tokens, cache, 0)[0], params)
    assert fwd_bwd[0] > 0 and fwd[0] == 0
    got = tfm.apply_with_cache(cfg, params, tokens, cache, 0)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(tfm.apply(cfg, params, tokens)), atol=2e-5)


def test_the_dense_form_batches_over_experts_from_64_rows(cfg, params):
    """``experts_dense`` at 63 rows (the rows handed over once for all experts) and
    at 64 (once an expert: a batched product, which reads each expert's bank where
    it lies): the same numbers, and the jaxpr says which form each took."""
    bank = jax.tree.map(lambda a: a[1], params["moe"]["experts"])
    n = dropless.BATCHED_ROWS
    x = jax.random.normal(jax.random.PRNGKey(0), (n, 64))
    weights = jax.random.uniform(jax.random.PRNGKey(1), (n, 4))
    experts = jnp.argsort(jax.random.uniform(jax.random.PRNGKey(2), (n, 16)))[:, :4].astype(jnp.int32)
    whole = dropless.experts_dense(bank, x, weights, experts)
    fewer = dropless.experts_dense(bank, x[:-1], weights[:-1], experts[:-1])
    assert float(jnp.std(whole)) > 0.01
    np.testing.assert_allclose(np.asarray(whole[:-1]), np.asarray(fewer), atol=2e-5)
    batched = lambda *a: str(jax.make_jaxpr(dropless.experts_dense)(bank, *a)).count(  # noqa: E731
        "dimension_numbers=(([2], [1]), ([0], [0]))")  # e a batch dimension of both operands
    assert batched(x, weights, experts) == 3 and batched(x[:-1], weights[:-1], experts[:-1]) == 1
