"""What the Ouro test files (and ``experiments/loop_chip.py``) share: the tiny twin's
program, reference, configuration and seeded parameters as module-scoped fixtures,
and the planted faults. Importing it puts the repo's root on ``sys.path``
(``chipbench`` is imported from there)."""

import contextlib
import inspect
import json
import os
import sys
from unittest import mock

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
# standard deviation 1 (measured here, PR 56: 3e-6 to 5e-6 over three passes of two layers;
# bfloat16 compute reads 5e-2 to 8e-2). Every planted fault below reads over 10 x this.
TOL = parity.TOL["cache"]
CONFIG = "ouro-2.6b-L12"


def _config(name=CONFIG):
    with open(os.path.join(ROOT, "chipbench", "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def program():
    return program_of(_config(), "rehearse_program")


@pytest.fixture(scope="module")
def reference(program):
    return load_reference(program)


@pytest.fixture(scope="module")
def cfg(program):
    return tfm.TransformerConfig(dtype=jnp.float32, **program)


@pytest.fixture(scope="module")
def params(cfg):
    return parity._seeded_params(tfm, cfg)  # noise on every leaf: norm scales and the gate's bias count too


def _tokens(cfg, shape, seed=0):
    return np.random.default_rng(seed).integers(0, cfg.vocab_size, size=shape).astype(np.int32)


PLANTED = {  # one line of the program wrong: (function, the line, what stands in its place)
    "one pass too few": ("_layer_loop", "jnp.arange(cfg.layer_passes, dtype=jnp.int32))",
                         "jnp.arange(cfg.layer_passes - 1, dtype=jnp.int32))"),
    "the norm between passes dropped": (
        "_layer_loop", "x, handed = after_pass(carry[0])",
        "x, handed = after_pass(carry[0]); "
        "x = jnp.where(r == cfg.layer_passes - 1, x, carry[0])"),
    "a branch norm dropped": ("_block", 'post(f, "ln2_post")', "f"),
    "a decode step reads the pass before": (
        "_cache_attention", "decode_attention(q[:, 0], k_stack, v_stack, pos, layer=l, walk=walk)",
        "decode_attention(q[:, 0], k_stack, v_stack, pos, walk=walk, "
        "layer=jnp.where(l >= cfg.num_layers, l - cfg.num_layers, l))"),
    # a fault of PRECISION, for the chip (float32 compute makes it no fault at all): the
    # norm between the passes in the compute dtype
    "the norm between passes in the compute dtype": (
        "_after_pass", "h = _final_norm(cfg, params, x)",
        "h = (x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) "
        "+ jnp.asarray(cfg.layernorm_epsilon, x.dtype)) "
        "* params['lnf_scale'].astype(x.dtype)).astype(x.dtype)"),
}


def in_float8(params):
    """The tree with its matrices (the layers' stacks and the head) rounded to float8
    (e4m3), the nearest precision below the configuration's bfloat16. The control of
    the check's limit: the REFERENCE run on this tree stands in the probe's place and
    ``judge`` holds it against the reference on the tree as it is."""
    f8 = lambda x: x.astype(jnp.float8_e4m3fn).astype(jnp.float32)  # noqa: E731
    return {**params, "lm_head": f8(params["lm_head"]),
            "layers": {k: (f8(v) if v.ndim >= 3 else v) for k, v in params["layers"].items()}}


def judge_float8_reference(reference, program, params, prompts, got):
    """``serve_recurrent.judge`` (under whatever limits are patched in) with the float8
    reference's logits as the probe, at the rows the probe would give."""
    from chipbench.drivers import serve, serve_recurrent

    seqs = [np.concatenate([p, g[:serve.DECODE_STEPS]]) for p, g in zip(prompts, got)]
    rows = [np.arange(len(p) - 1, len(p) + serve.DECODE_STEPS) for p in prompts]
    low = reference.logits_of(program, in_float8(params), seqs, rows, fetch=WHOLE)
    return serve_recurrent.judge(reference, program, params, prompts, got, low)


@contextlib.contextmanager
def planted(fault):
    """``tfm``'s function with one line replaced, as the module would have it, for as
    long as the context is open. Programs traced inside it carry the fault."""
    name, old, new = PLANTED[fault]
    source = inspect.getsource(getattr(tfm, name))
    assert source.count(old) == 1, (name, old)
    scope = dict(vars(tfm))
    exec(source.replace(old, new), scope)  # noqa: S102 -- the module's own source, one line changed
    with mock.patch.object(tfm, name, scope[name]):
        yield
