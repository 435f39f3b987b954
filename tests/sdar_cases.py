"""What the SDAR test files (and ``experiments/block_chip.py``) share: the tiny twin's
program WITH the block mask (``rehearse_blocks_program``, B = 4), reference, configuration
and seeded parameters as module-scoped fixtures, the helpers that take a prompt and its
blocks through the slot cache pass by pass, and the planted faults. Importing it puts the
repo's root on ``sys.path`` (``chipbench`` is imported from there)."""

import contextlib
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
from chipbench.references import Program, load_reference, program_of  # noqa: E402
from deepspeed_tpu.inference import serving  # noqa: E402
from deepspeed_tpu.models import transformer as tfm  # noqa: E402

WHOLE = lambda leaves: leaves  # noqa: E731
# Float32 on both sides differ by summation order alone: ``parity.TOL``'s 1e-4 on logits of
# standard deviation 1 (measured here, PR 63: 5e-6 to 9e-6 over three layers; every planted
# fault below reads over 100 x this).
TOL = parity.TOL["cache"]
CONFIG = "sdar-30b-a3b-L7"
BLOCK = 4  # the blocks twin's


def _config(name=CONFIG):
    with open(os.path.join(ROOT, "chipbench", "configs", f"{name}.json")) as f:
        return json.load(f)


def program_at(block: int) -> Program:
    """The twin at another block length (1: the causal backbone, no block key)."""
    base = dict(_config()["rehearse_program"])
    if block > 1:
        base.update(attn_block_length=block, mask_token_id=base["vocab_size"] - 1)
    return Program(base, "sdar_moe")


@pytest.fixture(scope="module")
def program():
    return program_of(_config(), "rehearse_blocks_program")


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
    """Seeded ids of the whole vocabulary, the mask token's own among them."""
    return np.random.default_rng(seed).integers(0, cfg.vocab_size, size=shape).astype(np.int32)


def _bucket(n: int, least: int = 8) -> int:
    return max(least, 1 << (n - 1).bit_length())


def through_cache(cfg, params, passes, prompt, *, slots=3, slot=1, smax=128,
                  skip_commit=False, write_off=0):
    """The serving path's own computation for one request, by the program's helpers: the
    prompt's whole blocks bucket-padded into a local cache and written into ``slot`` of a
    slot cache (``SlotWorker._build_prefill``), then every pass of ``passes`` (the
    reference's ``generate`` records: the sequence as it stood) as a block step over
    [slots, B] rows at per-row ``pos`` / ``write_pos``, the other rows idle
    (``_build_block_step``) -> the block rows' logits of each pass [B, V]. ``skip_commit``
    / ``write_off``: planted faults."""
    B = cfg.attn_block_length
    whole = len(prompt) - len(prompt) % B
    cache = tfm.init_cache(cfg, slots, smax, dtype=cfg.dtype)
    if whole:
        bucket = _bucket(whole)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :whole] = prompt[:whole]
        local = tfm.init_cache(cfg, 1, bucket, dtype=cfg.dtype)
        _, local = tfm.apply_with_cache(cfg, params, padded, local, 0, last_index=whole - 1)
        cache = tfm.update_cache_slot(cache, local, slot)

    @jax.jit
    def step(cache, toks, pos, wpos):
        logits, cache = tfm.apply_with_cache(cfg, params, toks, cache, pos, write_pos=wpos)
        return logits, cache

    out = []
    for p in passes:
        if skip_commit and p["commit"]:
            out.append(None)
            continue
        toks = np.zeros((slots, B), np.int32)
        toks[slot] = p["sequence"][p["start"]:]
        pos = np.zeros((slots,), np.int32)
        pos[slot] = p["start"]
        wpos = np.full((slots,), smax, np.int32)
        wpos[slot] = p["start"] + write_off
        logits, cache = step(cache, toks, pos, wpos)
        out.append(np.asarray(logits[slot], np.float32))
    return out


# -- planted faults ------------------------------------------------------------------------

@contextlib.contextmanager
def causal_inside_a_block():
    """The causal mask inside a block: a row sees the keys at or under its own position."""
    with mock.patch.object(tfm, "block_visible", lambda q_pos, block: q_pos):
        yield


def _worker_step(edit):
    """``SlotWorker.block_step`` with its operands edited: (opened, new_toks, new_mask, pos,
    wpos, active, count, threshold, ...) -> the same."""
    real = serving.SlotWorker.block_step

    def planted(self, *operands, **kw):
        return real(self, *edit(self, list(operands)), **kw)

    return mock.patch.object(serving.SlotWorker, "block_step", planted)


def commit_skipped():
    """The engine's commit passes write nothing: the K/V a later block reads is what the
    last denoising pass left, computed from a block that still held a mask."""
    def edit(worker, ops):
        commits = np.asarray(ops[5]) & (np.asarray(ops[6]) == 0)
        ops[4] = np.where(commits, worker.Smax, ops[4]).astype(np.int32)
        return ops

    return _worker_step(edit)


def written_one_off():
    """Every block's K/V one position off."""
    def edit(worker, ops):
        ops[4] = np.where(np.asarray(ops[5]), np.asarray(ops[4]) + 1, ops[4]).astype(np.int32)
        return ops

    return _worker_step(edit)
