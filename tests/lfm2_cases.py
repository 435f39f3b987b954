"""What the LFM2 test files share (PR 47 split ``test_lfm2.py`` by program family):
the twin's program, reference, configuration and seeded parameters as module-scoped
fixtures, and the helpers more than one of the files call. Importing it puts the
repo's root on ``sys.path`` (``chipbench`` is imported from there)."""

import inspect
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
from chipbench.drivers import serve_shortconv  # noqa: E402
from chipbench.references import load_reference, program_of  # noqa: E402
from deepspeed_tpu.models import transformer as tfm  # noqa: E402


WHOLE = lambda leaves: leaves  # noqa: E731
TOL = parity.TOL["apply"]  # float32 on both sides, summation order alone
CONFIG = "lfm2-24b-a2b-L9"


def _config():
    with open(os.path.join(ROOT, "chipbench", "configs", f"{CONFIG}.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def program():
    return program_of(_config(), serve_shortconv.TWIN)


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


_PLANTED = {  # one line of ``_short_conv`` / ``_filter_tail`` / the configuration wrong
    "the input gate dropped": ("_short_conv", "u = gate_in * z", "u = z"),
    "the output gate dropped": ("_short_conv", "gate_out * c.astype(h.dtype)",
                                "c.astype(h.dtype)"),
    "the taps out of order": ("_causal_filter", "* taps[j] for j", "* taps[K - 1 - j] for j"),
    "the state taken from the padding": ("_filter_tail", "if live is None else",
                                         "if True else"),
}


def _plant(monkeypatch, fault):
    """``tfm``'s function with one line replaced, as the module would have it."""
    name, old, new = _PLANTED[fault]
    source = inspect.getsource(getattr(tfm, name))
    assert source.count(old) == 1, (name, old)
    scope = dict(vars(tfm))
    exec(source.replace(old, new), scope)  # noqa: S102 -- the module's own source, one line changed
    monkeypatch.setattr(tfm, name, scope[name])
