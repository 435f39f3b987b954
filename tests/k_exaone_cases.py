"""What the K-EXAONE test files share (PR 47 split ``test_k_exaone.py`` by program family):
the twin's program, reference, configuration and seeded parameters as module-scoped
fixtures, and the helpers more than one of the files call. Importing it puts the
repo's root on ``sys.path`` (``chipbench`` is imported from there)."""

import json
import os
import sys

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
TOL = parity.TOL["apply"]  # float32 on both sides, summation order alone
CONFIG = "k-exaone-236b-a23b-L5"
WINDOW = 16  # the kinds twin's


def _config():
    with open(os.path.join(ROOT, "chipbench", "configs", f"{CONFIG}.json")) as f:
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


def _bucket(n: int) -> int:
    return max(16, 1 << (n - 1).bit_length())


def _prefill(cfg, params, cache, slot, prompt):
    n, bucket = len(prompt), _bucket(len(prompt))
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n] = prompt
    local = tfm.init_cache(cfg, 1, bucket)
    logits, local = tfm.apply_with_cache(cfg, params, padded, local, 0, last_index=n - 1,
                                         live=jnp.arange(bucket)[None, :] < n)
    return np.asarray(logits[0, 0]), tfm.update_cache_slot(cache, local, slot)
