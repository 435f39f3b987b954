"""What the Mellum2 test files (and ``experiments/chunk_chip.py``) share: the tiny twin's
program (WITH window layers and both rotaries: ``rehearse_kinds_program``), reference,
configuration and seeded parameters as module-scoped fixtures, the helpers that take a
prompt through the cache whole or in chunks, and the planted faults. Importing it puts the
repo's root on ``sys.path`` (``chipbench`` is imported from there)."""

import contextlib
import inspect
import json
import os
import sys
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import deepspeed_tpu  # noqa: E402,F401
from chipbench import parity  # noqa: E402
from chipbench.references import load_reference, program_of  # noqa: E402
from deepspeed_tpu.models import transformer as tfm  # noqa: E402

WHOLE = lambda leaves: leaves  # noqa: E731
# Float32 on both sides differ by summation order alone: ``parity.TOL``'s 1e-4 on logits of
# standard deviation 1 (measured here, PR 59: 4e-6 to 6e-6 over eight layers; every planted
# fault below reads over 10 x this).
TOL = parity.TOL["cache"]
CONFIG = "mellum2-12b-a2.5b-L8"
WINDOW = 16  # the kinds twin's


def _config(name=CONFIG):
    with open(os.path.join(ROOT, "chipbench", "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def program():
    return program_of(_config(), "rehearse_kinds_program")


@pytest.fixture(scope="module")
def reference(program):
    return load_reference(program)


@pytest.fixture(scope="module")
def cfg(program):
    return tfm.TransformerConfig(dtype=jnp.float32, **program)


@pytest.fixture(scope="module")
def params(cfg):
    return parity._seeded_params(tfm, cfg)  # noise on every leaf: norm scales count too


def _tokens(cfg, shape, seed=0):
    return np.random.default_rng(seed).integers(0, cfg.vocab_size, size=shape).astype(np.int32)


def _bucket(n: int, least: int = 8) -> int:
    return max(least, 1 << (n - 1).bit_length())


def segments(n: int, chunk: int) -> list:
    """``ServingEngine._segments``'s cut of a prompt of ``n`` tokens: whole chunks, then
    ONE padded power-of-two tail -> [(start, width, live)]."""
    cuts = [(p, chunk, chunk) for p in range(0, n - chunk + 1, chunk)]
    rest = n - len(cuts) * chunk
    if rest:
        cuts.append((len(cuts) * chunk, min(_bucket(rest), chunk), rest))
    return cuts


def _programs(cfg):
    """(chunk, step, prefill), each traced where it is first called (inside ``planted``'s
    context where a test opened one) and jitted: run op by op, a prompt's chunks load
    thousands of tiny executables and the process runs out of memory mappings."""
    chunk = jax.jit(lambda params, block, cache, start, live: tfm.apply_with_cache(
        cfg, params, block, cache, jnp.reshape(start, (1,)),
        live=jnp.arange(block.shape[1])[None, :] < live))
    step = jax.jit(lambda params, tok, cache, pos: tfm.apply_with_cache(
        cfg, params, jnp.reshape(tok, (1, 1)), cache, pos, write_pos=pos))
    prefill = jax.jit(lambda params, padded, live: tfm.apply_with_cache(
        cfg, params, padded, tfm.init_cache(cfg, 1, padded.shape[1]), 0,
        live=jnp.arange(padded.shape[1])[None, :] < live))
    return chunk, step, prefill


def _decode(step, params, cache, n, steps, rows):
    pos = jnp.asarray([n], jnp.int32)
    for tok in steps:
        logits, cache = step(params, jnp.int32(tok), cache, pos)
        rows.append(np.asarray(logits[0]))
        pos = pos + 1
    return np.concatenate(rows), cache


def chunked(cfg, params, prompt, chunk: int, smax: int, steps=()):
    """A prompt through ``SlotWorker._build_chunk``'s computation, chunk by chunk into row
    0 of a slot cache ``smax`` long, then one decode step a token of ``steps`` -> (logits of
    every LIVE prompt row and of every step [len(prompt) + len(steps), V], the cache)."""
    run, step, _ = _programs(cfg)
    cache = tfm.init_cache(cfg, 1, smax)
    rows = []
    for start, width, live in segments(len(prompt), chunk):
        block = np.zeros((1, width), np.int32)
        block[0, :live] = prompt[start:start + live]
        logits, cache = run(params, block, cache, jnp.int32(start), jnp.int32(live))
        rows.append(np.asarray(logits[0, :live]))
    return _decode(step, params, cache, len(prompt), steps, rows)


def whole_prompt(cfg, params, prompt, smax: int, steps=()):
    """The same through ONE bucket-padded prefill into a local cache written to the slot
    (``SlotWorker._build_prefill``), then the decode steps."""
    _, step, prefill = _programs(cfg)
    n, bucket = len(prompt), _bucket(len(prompt), 16)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n] = prompt
    logits, local = prefill(params, padded, jnp.int32(n))
    cache = tfm.update_cache_slot(tfm.init_cache(cfg, 1, smax), local, 0)
    return _decode(step, params, cache, n, steps, [np.asarray(logits[0, :n])])


PLANTED = {  # one line of the program wrong: (function, the line, what stands in its place)
    "plain rotary on the full layers": (
        "rotary_tables", "for window in (False, True))", "for window in (True, True))"),
    "attention_factor dropped": (
        "rotary_table", "plain * (1 - r), yarn_attention_factor(spec)", "plain * (1 - r), 1.0"),
    "the window off by one": (
        "_cache_attention", "        ring = stacks[RING]\n",
        "        ring = stacks[RING]; window = window - 1\n"),
    "a ring that a chunk overwrote before its queries read it": (
        "_cache_attention", "jnp.take_along_axis(ring_l[name], order, axis=1)",
        "jnp.take_along_axis(written[name], order, axis=1)"),
    "a chunk's padded rows written into the ring": (
        "_cache_attention", "p = held(start + live_rows() - 1)", "p = held(start + T - 1)"),
}
# the five wrong programs ISSUE 59 names for the chip (the fifth is ``fetch_float8``'s)
CHIP_FAULTS = tuple(PLANTED)[:4]


def fetch_float8(leaves):
    """A reference's ``fetch`` that rounds every MATRIX it is handed (the layers' projections,
    the router, an expert's three, the head's columns; not the norms' scales, not the
    embedding's rows) to float8 (e4m3), the nearest precision below the configuration's
    bfloat16, where it is fetched: the tree on the device is never copied (a float8 copy of
    7.6 GB of weights beside the served model does not fit the chip). The control of the
    check's limit: the REFERENCE through this fetch stands in the probe's place and
    ``judge`` holds it against the reference on the tree as it is."""
    f8 = lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype)  # noqa: E731
    return {k: (f8(v) if v.ndim >= 2 and k != "rows" else v) for k, v in leaves.items()}


def judge_float8_reference(reference, program, params, prompts, got, engine_chosen):
    """``serve_latent.judge`` (under whatever limits are patched in) with the float8
    reference's logits and ITS choices as the probe's."""
    from chipbench.drivers import serve, serve_latent

    seqs = [np.concatenate([p, g[:serve.DECODE_STEPS]]) for p, g in zip(prompts, got)]
    rows = [np.arange(len(p) - 1, len(p) + serve.DECODE_STEPS) for p in prompts]
    low = reference.routed_passes(program, params, seqs, rows, fetch=fetch_float8)
    return serve_latent.judge(reference, program, params, prompts, got, low["logits"],
                              low["own"], engine_chosen)


@contextlib.contextmanager
def planted(fault):
    """``tfm``'s function with one line replaced, as the module would have it, for as
    long as the context is open. Programs traced inside it carry the fault."""
    name, old, new = PLANTED[fault]
    source = inspect.getsource(getattr(tfm, name))
    assert source.count(old) == 1, (name, old, source.count(old))
    scope = dict(vars(tfm))
    exec(source.replace(old, new), scope)  # noqa: S102 -- the module's own source, one line changed
    with mock.patch.object(tfm, name, scope[name]):
        yield
