"""What an inference engine holds: ``transformer.hold_for_compute`` puts every
leaf in the dtype the forward pass reads it in, so no program casts a weight
and no output changes. Every ``chipbench/configs/*.json`` at its rehearsal
size, bf16 compute, on seeded noisy weights (``chipbench.parity``: norm scales
and biases are not at ``init``'s 1 and 0, which bf16 holds exactly)."""
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import parity
from chipbench.references import program_of
from deepspeed_tpu.inference import InferenceEngine
from deepspeed_tpu.models import transformer as tfm

CONFIGS = sorted(os.path.basename(p)[:-5]
                 for p in glob.glob(os.path.join(parity.HERE, "configs", "*.json")))
ROUTED = [c for c in CONFIGS if c.startswith(("olmoe", "kanana"))]
# read in float32 by the forward pass; everything else floating is cast to cfg.dtype
FLOAT32_LEAVES = {"ln1_scale", "ln1_bias", "ln2_scale", "ln2_bias", "lnf_scale", "lnf_bias",
                  "emb_ln_scale", "emb_ln_bias", "q_norm_scale", "k_norm_scale",
                  "lm_head_bias", "gate",
                  # latent attention's norm over the latent; a router's selection bias (PR 31)
                  "kv_norm_scale", "bias",
                  # the sandwich's branch norms (PR 56)
                  "ln1_post_scale", "ln2_post_scale"}
# ... and whole groups, by the key they hang under: the exit gate's [d, 1] map and its bias (PR 56)
FLOAT32_GROUPS = {"exit_gate"}
PROMPT, STEPS = 24, 3


def _cfg(config: str, dtype=jnp.bfloat16) -> tfm.TransformerConfig:
    with open(os.path.join(parity.HERE, "configs", f"{config}.json")) as f:
        return tfm.TransformerConfig(dtype=dtype, **program_of(json.load(f), "rehearse_program"))


def _params(cfg):
    params = parity._seeded_params(tfm, cfg)
    params["lm_head_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(3), (cfg.vocab_size,))
    return params


def _dtypes(tree) -> dict:
    return {jax.tree_util.keystr(path): leaf.dtype.name
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


def _serve(cfg, params) -> np.ndarray:
    """Logits of a two-row prefill and of STEPS decode steps at per-row
    positions through the cache, as the serving programs compute them."""
    def run(params, tokens, forced):
        cache = tfm.init_cache(cfg, 2, 64)
        logits, cache = tfm.apply_with_cache(cfg, params, tokens, cache, 0)
        out = [logits[:, -1]]
        for i in range(STEPS):
            pos = jnp.full((2,), PROMPT + i, jnp.int32)
            logits, cache = tfm.apply_with_cache(cfg, params, forced[:, i, None], cache, pos)
            out.append(logits[:, 0])
        return jnp.stack(out)

    rng = np.random.default_rng(28)
    tokens = rng.integers(0, cfg.vocab_size, size=(2, PROMPT)).astype(np.int32)
    forced = rng.integers(0, cfg.vocab_size, size=(2, STEPS)).astype(np.int32)
    return np.asarray(jax.jit(run)(params, tokens, forced))


def _rounded(params, path: tuple):
    """``params`` with the leaf at ``path`` rounded to bf16 and back."""
    out = jax.tree.map(lambda x: x, params)
    node = out
    for key in path[:-1]:
        node = node[key]
    node[path[-1]] = node[path[-1]].astype(jnp.bfloat16).astype(jnp.float32)
    return out


@pytest.mark.parametrize("config", CONFIGS)
def test_held_leaves_are_in_the_dtype_the_forward_pass_reads(config):
    cfg = _cfg(config)
    params = _params(cfg)
    held = tfm.hold_for_compute(cfg, params)
    assert jax.tree.structure(held) == jax.tree.structure(params)
    for path, leaf in jax.tree_util.tree_leaves_with_path(held):
        in_float32 = path[-1].key in FLOAT32_LEAVES or path[0].key in FLOAT32_GROUPS
        want = "float32" if in_float32 else "bfloat16"
        assert leaf.dtype.name == want, jax.tree_util.keystr(path)
    assert {"bfloat16", "float32"} == set(_dtypes(held).values())


@pytest.mark.parametrize("config", CONFIGS)
def test_float32_compute_holds_the_tree_as_it_is(config):
    cfg = _cfg(config, jnp.float32)
    params = _params(cfg)
    params["layers"]["_local"] = jnp.arange(cfg.num_layers, dtype=jnp.int32)  # an integer leaf
    held = tfm.hold_for_compute(cfg, params)
    assert _dtypes(held) == _dtypes(params)
    assert all(a is b for a, b in zip(jax.tree.leaves(held), jax.tree.leaves(params)))


@pytest.mark.parametrize("config", CONFIGS)
def test_held_tree_gives_the_float32_trees_logits_bit_for_bit(config):
    cfg = _cfg(config)
    params = _params(cfg)
    want = _serve(cfg, params)
    assert np.isfinite(want).all() and want.std() > 0
    np.testing.assert_array_equal(_serve(cfg, tfm.hold_for_compute(cfg, params)), want)


@pytest.mark.parametrize("config,leaf", [(c, ("layers", "ln2_scale")) for c in CONFIGS]
                         + [(c, ("moe", "gate")) for c in ROUTED],
                         ids=lambda v: v if isinstance(v, str) else ".".join(v))
def test_a_float32_leaf_rounded_to_bf16_changes_the_logits(config, leaf):
    """The controls of the equality above: were a norm scale or the router
    held in bf16, the outputs would be others."""
    cfg = _cfg(config)
    params = _params(cfg)
    assert not np.array_equal(_serve(cfg, _rounded(params, leaf)), _serve(cfg, params))


def test_gshard_bank_is_held_like_the_dropless_one():
    cfg = tfm.TransformerConfig(vocab_size=97, max_seq_len=64, num_layers=2, num_heads=2,
                                hidden_size=32, moe_every=2, num_experts=4, moe_top_k=2,
                                dtype=jnp.bfloat16)
    params = parity._seeded_params(tfm, cfg)
    held = tfm.hold_for_compute(cfg, params)
    assert held["moe"]["gate"].dtype == jnp.float32
    assert {x.dtype.name for x in jax.tree.leaves(held["moe"]["experts"])} == {"bfloat16"}
    np.testing.assert_array_equal(_serve(cfg, held), _serve(cfg, params))


def test_quantised_storage_passes_through():
    cfg = _cfg(CONFIGS[0])
    params = tfm.quantize_weights(cfg, tfm.init(cfg, jax.random.PRNGKey(0)), bits=8, group_size=16)
    held = tfm.hold_for_compute(cfg.replace(weight_bits=8, weight_group_size=16), params)
    assert held["layers"]["wq"]["q"].dtype == jnp.int8
    assert held["layers"]["wq"]["s"].dtype == params["layers"]["wq"]["s"].dtype == jnp.float32
    assert held["layers"]["ln1_scale"].dtype == jnp.float32 and held["wte"].dtype == jnp.bfloat16


@pytest.mark.parametrize("config", CONFIGS)
def test_engine_holds_the_same_tree_with_and_without_params(config):
    """One rule in both branches of ``InferenceEngine``: drawn in the engine, or
    handed in as float32 numpy leaves (a checkpoint), the leaves have the same
    dtypes. The host's rounding is the device's, bit for bit; the engine's own
    draw is one fused program, whose float32 values may differ from an eager
    ``init`` in the last place before they are rounded."""
    cfg = _cfg(config)
    leaves = tfm.init(cfg, jax.random.PRNGKey(0))
    want = tfm.hold_for_compute(cfg, leaves)
    drawn = InferenceEngine(model=tfm.Model(cfg), config={"dtype": "bf16"})
    given = InferenceEngine(model=tfm.Model(cfg), config={"dtype": "bf16"},
                            params=jax.tree.map(np.asarray, leaves))
    assert _dtypes(drawn.params) == _dtypes(given.params) == _dtypes(want)
    for a, b, c in zip(*map(jax.tree.leaves, (given.params, want, drawn.params))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_allclose(np.asarray(c, np.float32), np.asarray(b, np.float32),
                                   rtol=2.0 ** -7, atol=1e-6)
    full = InferenceEngine(model=tfm.Model(cfg), config={"dtype": "fp32"})
    assert set(_dtypes(full.params).values()) == {"float32"}
