"""What the Falcon-H1 test files share (PR 47 split ``test_falcon_h1.py`` by program family):
the twin's program, reference, configuration and seeded parameters as module-scoped
fixtures, and the helpers more than one of the files call. Importing it puts the
repo's root on ``sys.path`` (``chipbench`` is imported from there)."""

import json
import os
import sys

import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import parity  # noqa: E402
from chipbench.references import Program, load_reference  # noqa: E402
from deepspeed_tpu.models import transformer as tfm  # noqa: E402


WHOLE = lambda leaves: leaves  # noqa: E731
TOL = parity.TOL["apply"]  # float32 on both sides, summation order alone
CONFIG = "falcon-h1-34b-L4"
SMAX = 384


def _config():
    with open(os.path.join(ROOT, "chipbench", "configs", f"{CONFIG}.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def program():
    """The twin WITH the mixer (``rehearse_program`` is the one without: the
    configuration's notes say why)."""
    return Program(_config()["rehearse_recurrent_program"], "falcon_h1")


@pytest.fixture(scope="module")
def reference(program):
    return load_reference(program)


@pytest.fixture(scope="module")
def cfg(program):
    return tfm.TransformerConfig(dtype=jnp.float32, **program)


@pytest.fixture(scope="module")
def params(cfg):
    return parity._seeded_params(tfm, cfg)  # noise on every leaf: norm scales count too
