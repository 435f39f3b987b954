"""What the Qwen3-Next test files share: the delta twin's program, reference,
configuration and seeded parameters as module-scoped fixtures, and the helpers more
than one of the files call. Importing it puts the repo's root on ``sys.path``
(``chipbench`` is imported from there)."""

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
from chipbench.drivers import serve_delta  # noqa: E402
from chipbench.references import load_reference, program_of  # noqa: E402
from deepspeed_tpu.models import transformer as tfm  # noqa: E402


WHOLE = lambda leaves: leaves  # noqa: E731
# Float32 on both sides, the rule's chunked form against its recurrence: summation order and
# the chunk's inverse (PR 52: 3e-4 to 6e-4 on logits of standard deviation 1 over 150 rows and
# eight layers, 1e-5 a layer on outputs of 3 to 4; the all-attention twin reads 7e-6 through
# ``parity.TOL``). bfloat16 compute reads over 3e-2 (``test_bfloat16_compute_fails...``).
TOL = 2e-3
LOSS_TOL = parity.TOL["loss"]
CONFIG = "qwen3-next-80b-a3b-L8"
OPS = ["delta", "delta", "delta", "attn"] * 2  # the twin's


def _config():
    with open(os.path.join(ROOT, "chipbench", "configs", f"{CONFIG}.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def program():
    return program_of(_config(), serve_delta.TWIN)


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


_PLANTED = {  # one line of the program wrong
    "beta skipped": ("_gated_delta", "beta = jax.nn.sigmoid(ba[..., :Hv])",
                     "beta = jnp.ones_like(ba[..., :Hv])"),
    "the decay dropped": ("_gated_delta", "g = -jnp.exp(lp[\"delta_a_log\"].astype(f32)) * ",
                          "g = 0.0 * "),
    "the norm behind the gate": ("_gated_delta", "jnp.mean(jnp.square(o), axis=-1, keepdims=True)",
                                 "jnp.mean(jnp.square(o * jax.nn.silu(proj[..., conv_dim:].astype("
                                 "f32).reshape(B_, T, Hv, D))), axis=-1, keepdims=True)"),
    "the attention gate dropped": ("_attn_out_proj", "if gate is not None:", "if False:"),
    "the shared expert's gate dropped": None,  # in moe/dropless.py: planted by hand below
    "the state taken from the padding": ("_filter_tail", "if live is None else", "if True else"),
}


def _plant(monkeypatch, fault):
    """``tfm``'s function with one line replaced, as the module would have it."""
    if fault == "the shared expert's gate dropped":
        from deepspeed_tpu.moe import dropless

        real = dropless.shared_expert
        monkeypatch.setattr(dropless, "shared_expert", lambda w, x: real(
            {k: v for k, v in w.items() if k != "w_gate"}, x))
        return
    name, old, new = _PLANTED[fault]
    source = inspect.getsource(getattr(tfm, name))
    assert source.count(old) == 1, (name, old)
    scope = dict(vars(tfm))
    exec(source.replace(old, new), scope)  # noqa: S102 -- the module's own source, one line changed
    monkeypatch.setattr(tfm, name, scope[name])
