"""Ouro's tiny twin through the cache (PR 56): the cache keeps the passes APART. A
prompt prefilled in one block, in two chunks and token by token gives the same K/V in
all ``layer_passes`` x L cache layers and the reference's logits; the leading axis is
pass-major; each planted fault of the passes fails the comparison in float32 by far."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ouro_cases import (  # noqa: F401,I001 -- first: puts the repo's root on sys.path; fixtures
    ROOT, WHOLE, TOL, program, reference, cfg, params, _tokens, planted)

from chipbench import parity  # noqa: E402
from chipbench.drivers import serve, serve_recurrent  # noqa: E402
from deepspeed_tpu.models import transformer as tfm  # noqa: E402

N, SMAX = 37, 128


def _prefill_in_blocks(cfg, params, tokens, cuts):
    """``tokens`` [N] into a fresh cache of one row in the blocks ``cuts`` gives
    (per-row positions, as a chunk enters) -> (logits of every position [N, V], cache)."""
    cache = tfm.init_cache(cfg, 1, SMAX)
    out, start = [], 0
    for end in cuts:
        logits, cache = tfm.apply_with_cache(cfg, params, tokens[None, start:end], cache,
                                             jnp.asarray([start], jnp.int32))
        out.append(np.asarray(logits[0]))
        start = end
    return np.concatenate(out), cache


@pytest.fixture(scope="module")
def one_block(cfg, params):
    tokens = _tokens(cfg, (N,), 11)
    return tokens, *_prefill_in_blocks(cfg, params, tokens, [N])


def test_the_cache_has_more_layers_than_the_weights(cfg, params, one_block):
    _, _, cache = one_block
    depth = cfg.layer_passes * cfg.num_layers
    assert params["layers"]["wq"].shape[0] == cfg.num_layers == 2 and depth == 6
    assert cache["k"].shape == cache["v"].shape == (depth, 1, SMAX, 4, 16)
    written = np.asarray(jnp.any(cache["k"][:, 0, :N] != 0, axis=(1, 2, 3)))
    assert written.all() and not np.asarray(cache["k"][:, 0, N:]).any()  # every (pass, layer)
    k = np.asarray(cache["k"][:, 0, :N])
    for a in range(depth):  # and no two of them alike: a pass's K/V are functions of ITS input
        for b in range(a):
            assert np.abs(k[a] - k[b]).max() > 1e-2, (a, b)


def test_blocks_chunks_and_steps_fill_the_same_cache(cfg, params, program, reference, one_block):
    """One block, two chunks (20 + 17) and token by token: the same K/V in all six
    cache layers and the reference's logits at every position."""
    tokens, logits, cache = one_block
    want = reference.logits_at(program, params, tokens, np.arange(N), fetch=WHOLE)
    assert np.abs(logits - want).max() <= TOL
    for cuts in ([20, N], list(range(1, N + 1))):
        got, other = _prefill_in_blocks(cfg, params, tokens, cuts)
        assert np.abs(got - want).max() <= TOL, cuts
        for name in ("k", "v"):
            assert np.abs(np.asarray(other[name]) - np.asarray(cache[name])).max() <= 1e-5, cuts


def test_the_leading_axis_is_pass_major(cfg, params, one_block):
    """Pass r of layer l lies at r x L + l, not at l x passes + r: the first L cache
    layers are the ONE-pass model's, and what the next pass wrote begins behind them."""
    tokens, _, cache = one_block
    once = cfg.replace(layer_passes=1, exit_gate=False)
    _, first = tfm.apply_with_cache(once, params, tokens[None], tfm.init_cache(once, 1, SMAX), 0)
    L = cfg.num_layers
    for name in ("k", "v"):
        np.testing.assert_allclose(np.asarray(cache[name][:L]), np.asarray(first[name]), atol=1e-6)
    assert np.abs(np.asarray(cache["k"][L]) - np.asarray(first["k"][0])).max() > 1e-2


def test_the_slot_helpers_move_every_pass_of_a_slot(cfg, params, one_block):
    _, _, cache = one_block
    slots = tfm.init_cache(cfg, 3, SMAX)
    slots = tfm.update_cache_slot(slots, tfm.slice_cache_slot(cache, 0, 64), 2)
    back = tfm.slice_cache_slot(slots, 2, 64)
    assert back["k"].shape == (6, 1, 64, 4, 16)
    np.testing.assert_array_equal(np.asarray(back["v"]), np.asarray(cache["v"][:, :, :64]))
    assert not np.asarray(slots["k"][:, :2]).any()


def _probe_error(cfg, params, program, reference, seed=5):
    prompts = [_tokens(cfg, (n,), seed + n) for n, _ in parity.PROMPTS]
    forced = _tokens(cfg, (2, serve.DECODE_STEPS), seed)
    got = serve.probe_logits(cfg, params, prompts, [b for _, b in parity.PROMPTS], forced)
    want = [reference.logits_at(program, params, np.concatenate([p, f]),
                                np.arange(len(p) - 1, len(p) + serve.DECODE_STEPS), fetch=WHOLE)
            for p, f in zip(prompts, forced)]
    # the first row is the prefill's; the others the decode steps'
    return (max(float(np.abs(g[:1] - w[:1]).max()) for g, w in zip(got, want)),
            max(float(np.abs(g[1:] - w[1:]).max()) for g, w in zip(got, want)))


def test_the_serving_probe_holds(cfg, params, program, reference):
    prefill, steps = _probe_error(cfg, params, program, reference)
    assert max(prefill, steps) <= TOL


@pytest.mark.parametrize("fault", ["one pass too few", "the norm between passes dropped",
                                   "a branch norm dropped",
                                   "a decode step reads the pass before"])
def test_a_planted_fault_fails_the_serving_probe_by_far(cfg, params, program, reference, fault):
    """In float32, and so in any precision. A step that reads pass r - 1's K/V shows in
    the DECODE steps alone: a prefill attends to its own block in every pass."""
    with planted(fault):
        prefill, steps = _probe_error(cfg, params, program, reference)
    assert steps > 10 * TOL, (fault, prefill, steps)
    assert (prefill <= TOL) == (fault == "a decode step reads the pass before")


def test_the_n_prompt_probe_is_the_same_computation(cfg, params, program, reference):
    """``serve_recurrent.probe_logits`` (the cell's: three prompts, the live-row mask,
    ``update_cache_slot`` on whatever leaves the cache has) against the reference."""
    prompts = [_tokens(cfg, (n,), n) for n in (21, 50, 100)]
    forced = _tokens(cfg, (3, serve.DECODE_STEPS), 9)
    got = serve_recurrent.probe_logits(cfg, params, prompts, [32, 64, 128], forced)
    want = reference.logits_of(program, params,
                               [np.concatenate([p, f]) for p, f in zip(prompts, forced)],
                               [np.arange(len(p) - 1, len(p) + serve.DECODE_STEPS)
                                for p in prompts], fetch=WHOLE)
    assert max(float(np.abs(g - w).max()) for g, w in zip(got, want)) <= TOL


def test_return_exit_through_the_cache(cfg, params, program, reference, one_block):
    tokens, logits, _ = one_block
    out, _, p = tfm.apply_with_cache(cfg, params, tokens[None], tfm.init_cache(cfg, 1, SMAX), 0,
                                     return_exit=True)
    np.testing.assert_allclose(np.asarray(out[0]), logits, atol=1e-5)
    want = reference.exit_distribution(program, params, tokens, np.arange(N), fetch=WHOLE)
    assert np.abs(np.asarray(p[0]) - want).max() <= 1e-5
    np.testing.assert_allclose(np.asarray(p).sum(-1), 1.0, atol=1e-6)


def test_the_passes_are_one_loop_in_the_traced_program(cfg, params):
    """The jaxpr of a decode step has ONE outer scan of ``layer_passes`` trips round
    one inner scan of ``num_layers``: its text does not grow with the passes."""
    def text(c):
        cache = jax.eval_shape(lambda: tfm.init_cache(c, 2, SMAX))
        p = jax.eval_shape(lambda: tfm.init(c, jax.random.PRNGKey(0)))
        return str(jax.make_jaxpr(lambda p, t, k, pos: tfm.apply_with_cache(
            c, p, t, k, pos, write_pos=pos))(p, jax.ShapeDtypeStruct((2, 1), jnp.int32), cache,
                                             jax.ShapeDtypeStruct((2,), jnp.int32)))
    three, seven = text(cfg), text(cfg.replace(layer_passes=7))
    assert abs(len(seven) - len(three)) < 0.02 * len(three)
    assert three.count("scan[") == 2 and "length=3" in three and "length=2" in three
