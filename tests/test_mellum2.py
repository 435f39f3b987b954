"""Mellum2-12B-A2.5B on the normal path, the model itself: a rotary per layer KIND (YaRN's
blend on the whole-context layers, a plain base on the window layers) as configuration
data, the table worked by hand, ``apply`` and the loss against the plain reference, and
what is refused by name. The cache path is ``test_mellum2_cache.py``'s, the engine
``test_mellum2_engine.py``'s."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from mellum2_cases import (CONFIG, TOL, WHOLE, WINDOW, _config, _tokens, cfg, params,  # noqa: F401
                           planted, program, reference)

from chipbench import parity
from chipbench.references import load_reference, program_of
from deepspeed_tpu.models import transformer as tfm

PUBLISHED = {"type": "yarn", "base": 500000.0, "factor": 16.0,
             "original_max_position_embeddings": 8192, "beta_fast": 32.0, "beta_slow": 1.0}


def test_the_kinds_are_data(cfg):
    S, G = (WINDOW, True, "attn"), (0, True, "attn")
    assert cfg.layer_kinds == (S, S, S, G) * 2 and cfg.window_layers == (0, 1, 2, 4, 5, 6)
    assert cfg.rotary_spec(True) == {"base": 10000.0}
    assert cfg.rotary_spec(False)["type"] == "yarn"
    assert hash(cfg) == hash(cfg.replace())  # still a jit's static argument
    plain = cfg.replace(rotary_by_kind=None)
    assert plain.rotary_spec(True) == plain.rotary_spec(False) == {"base": cfg.rotary_base}
    assert tfm.rotary_tables(plain) is None
    assert tfm.rotary_kinds_fact(cfg) == "whole=yarn(10000, x4, 32) window=plain(10000)"
    assert tfm.rotary_kinds_fact(plain) == "plain(10000)"


def test_the_configuration_file_states_the_published_model():
    c = _config()
    assert c["reduced"] == ["num_hidden_layers"] and c["published"] == {"num_hidden_layers": 28}
    assert (c["num_experts"], c["num_experts_per_tok"], c["vocab_size"], c["sliding_window"]) == (
        64, 8, 98304, 1024)
    assert c["rope_parameters"]["full_attention"]["attention_factor"] == 1.2772588722239782
    assert c["rope_parameters"]["sliding_attention"] == {"rope_type": "default",
                                                         "rope_theta": 500000}
    p = c["program"]
    assert p["local_attn_layers"] == [int(t == "sliding_attention") for t in c["layer_types"][:8]]
    yarn = p["rotary_by_kind"]["whole"]
    full = c["rope_parameters"]["full_attention"]
    assert {k: yarn[k] for k in ("factor", "original_max_position_embeddings", "beta_fast",
                                 "beta_slow", "attention_factor")} == {
        k: full[k] for k in ("factor", "original_max_position_embeddings", "beta_fast",
                             "beta_slow", "attention_factor")}
    assert yarn["base"] == p["rotary_by_kind"]["window"]["base"] == full["rope_theta"]
    counts = load_reference(program_of(c)).param_counts(p)
    assert counts["total"] == 3_794_968_832 and counts["matmul_per_expert"] == 6_193_152


def test_yarn_table_worked_by_hand():
    """The published full layers' table at 128-wide heads: d(32) = 18.08 and d(1) = 34.98,
    so pairs 0 .. 18 keep their frequency, pairs 35 .. 63 are divided by 16, and pair i
    between them blends at (i - 18) / 17."""
    d = lambda n: 128 * math.log(8192 / (2 * math.pi * n)) / (2 * math.log(500000))
    assert (math.floor(d(32)), math.ceil(d(1))) == (18, 35)
    ramp = tfm.yarn_ramp(PUBLISHED, 128)
    assert ramp.shape == (64,) and not ramp[:19].any() and (ramp[35:] == 1).all()
    np.testing.assert_allclose(ramp[19:35], (np.arange(19, 35) - 18) / 17, rtol=1e-6)
    freqs, factor = tfm.rotary_table(PUBLISHED, 128)
    plain = 500000.0 ** (-2 * np.arange(64) / 128)
    np.testing.assert_allclose(freqs[:19], plain[:19], rtol=2e-6)
    np.testing.assert_allclose(freqs[35:], plain[35:] / 16, rtol=2e-6)
    i = 27
    np.testing.assert_allclose(freqs[i], plain[i] * ((1 - 9 / 17) + (9 / 17) / 16), rtol=2e-6)
    assert factor == pytest.approx(0.1 * math.log(16) + 1) == pytest.approx(1.2772588722239782)
    assert tfm.yarn_attention_factor({**PUBLISHED, "attention_factor": 1.5}) == 1.5
    assert tfm.yarn_attention_factor({**PUBLISHED, "factor": 1.0}) == 1.0
    loose = tfm.yarn_ramp({**PUBLISHED, "truncate": False}, 128)  # low and high as they fall
    assert loose[18] == 0 and loose[19] == pytest.approx((19 - d(32)) / (d(1) - d(32)), rel=1e-5)
    assert loose[34] == pytest.approx((34 - d(32)) / (d(1) - d(32)), rel=1e-5) and loose[35] == 1


def test_a_plain_table_is_the_inline_formula_bit_for_bit():
    """The parent computed ``exp(-ln(base) i / half)`` inside ``rotary_embed``; the table of
    a plain spec is that expression, and ``rotary_embed`` without a table turns by it."""
    for base, rd in ((10000.0, 16), (500000.0, 128), (1e6, 24)):
        half = rd // 2
        inline = jnp.exp(-math.log(base) * jnp.arange(0, half, dtype=jnp.float32) / half)
        table, factor = tfm.rotary_table({"base": base}, rd)
        assert factor == 1.0 and np.array_equal(np.asarray(table), np.asarray(inline))
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 3, 16), jnp.bfloat16)
    pos = jnp.arange(9)[None] + jnp.asarray([[0], [500]])
    by_base = tfm.rotary_embed(x, pos, 16, False, 500000.0)
    by_table = tfm.rotary_embed(x, pos, 16, False, 1.0, table=tfm.rotary_table({"base": 500000.0}, 16))
    assert np.array_equal(np.asarray(by_base, np.float32), np.asarray(by_table, np.float32))


def test_a_factor_on_cos_and_sin_scales_the_scores_by_its_square():
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 5, 2, 16))
    pos = jnp.arange(5)[None] + 40
    table = tfm.rotary_table({**PUBLISHED, "original_max_position_embeddings": 32}, 16)
    turned = tfm.rotary_embed(x, pos, 16, table=table)
    unit = tfm.rotary_embed(x, pos, 16, table=(table[0], 1.0))
    np.testing.assert_allclose(np.asarray(turned), table[1] * np.asarray(unit), rtol=1e-5, atol=1e-6)


def test_apply_is_the_reference(cfg, params, program, reference):
    tokens = _tokens(cfg, (2, 96))
    got = np.asarray(tfm.apply(cfg, params, tokens))
    for j in range(2):
        want = reference.logits_at(program, params, tokens[j], np.arange(96), fetch=WHOLE)
        assert np.max(np.abs(got[j] - want)) <= TOL


def test_loss_is_the_reference(cfg, params, program, reference):
    tokens = _tokens(cfg, (2, 65), seed=3)
    got = float(tfm.causal_lm_loss(cfg, params, {"tokens": tokens}))
    assert abs(got - reference.lm_loss(program, params, tokens, fetch=WHOLE)) <= parity.TOL["loss"]


@pytest.mark.parametrize("fault", ["plain rotary on the full layers", "attention_factor dropped"])
def test_apply_with_a_wrong_rotary_is_not_the_reference(cfg, params, program, reference, fault):
    tokens = _tokens(cfg, (1, 96), seed=5)
    want = reference.logits_at(program, params, tokens[0], np.arange(96), fetch=WHOLE)
    with planted(fault):
        got = np.asarray(tfm.apply(cfg, params, tokens))[0]
    assert np.max(np.abs(got - want)) > 10 * TOL


def test_the_reference_parts_from_a_plain_rotary_past_the_original_context(program, reference,
                                                                         params, cfg):
    """Inside the original context the blend differs only where the ramp has begun; the
    reference under ``rotary_by_kind`` is not the reference without it."""
    tokens = _tokens(cfg, (96,), seed=7)
    plain = type(program)({k: v for k, v in program.items() if k != "rotary_by_kind"},
                          program.reference)
    a = reference.logits_at(program, params, tokens, np.arange(96), fetch=WHOLE)
    b = reference.logits_at(plain, params, tokens, np.arange(96), fetch=WHOLE)
    assert np.max(np.abs(a - b)) > 100 * TOL


_TWIN = dict(vocab_size=64, max_seq_len=64, num_layers=4, num_heads=2, hidden_size=32,
             pos_emb="rotary", local_attn_window=8, local_attn_layers=(1, 1, 1, 0))
_YARN = {"type": "yarn", "base": 10000.0, "factor": 4.0, "original_max_position_embeddings": 32}


@pytest.mark.parametrize("fields,error,words", [
    (dict(rotary_by_kind={"whole": _YARN}, pos_emb="learned"), ValueError, "no rotary to state"),
    (dict(rotary_by_kind={"full": _YARN}), ValueError, "'window' and 'whole'"),
    (dict(rotary_by_kind={"whole": {"type": "yarn", "base": 10000.0}}), ValueError, "factor"),
    (dict(rotary_by_kind={"whole": {"base": 0}}), ValueError, "base > 0"),
    (dict(rotary_by_kind={"whole": {"type": "longrope", "base": 1e4}}), NotImplementedError,
     "longrope"),
    (dict(rotary_by_kind={"whole": {**_YARN, "mscale": 1.0}}), NotImplementedError, "mscale"),
    (dict(rotary_by_kind={"window": {"base": 1e4}}, local_attn_layers=None), ValueError,
     "no layer attends inside a window"),
    (dict(rotary_by_kind={"whole": _YARN}, rotary_pct=0.5), NotImplementedError, "rotary_pct"),
    (dict(rotary_by_kind={"whole": _YARN}, rotary_interleaved=True), NotImplementedError,
     "rotary_interleaved"),
    (dict(rotary_by_kind={"whole": _YARN}, local_attn_layers=None, local_attn_window=0,
          kv_lora_rank=16, qk_head_dim=24, qk_rope_head_dim=8, v_head_dim=16, use_bias=False,
          decode_attn="xla"), NotImplementedError, "layer kinds|latent attention"),
])
def test_what_has_no_code_is_refused_by_name(fields, error, words):
    with pytest.raises(error, match=words):
        tfm.TransformerConfig(**{**_TWIN, **fields})


def test_a_pipeline_schedule_refuses_rotary_by_kind():
    cfg = tfm.TransformerConfig(**{**_TWIN, "rotary_by_kind": {"whole": _YARN}})
    with pytest.raises(NotImplementedError, match="rotary_by_kind"):
        tfm.refuse_in_pipeline(cfg)


def test_yarn_on_every_layer_of_a_model_without_windows(cfg, params):
    """One kind alone may state a rotary: a model whose every layer is whole-context turns
    by the ``whole`` kind's table in ``apply`` and through the cache alike."""
    one = tfm.TransformerConfig(**{**_TWIN, "local_attn_layers": None, "local_attn_window": 0,
                                   "rotary_by_kind": {"whole": _YARN}})
    p = tfm.init(one, jax.random.PRNGKey(0))
    tokens = _tokens(one, (1, 40))
    full = np.asarray(tfm.apply(one, p, tokens))
    logits, _ = tfm.apply_with_cache(one, p, tokens, tfm.init_cache(one, 1, 40), 0)
    assert np.max(np.abs(np.asarray(logits) - full)) <= TOL
    other = np.asarray(tfm.apply(one.replace(rotary_by_kind=None), p, tokens))
    assert np.max(np.abs(other - full)) > 100 * TOL


def _parent_rotary_embed(x, positions, rotary_dims, interleaved=False, base=10000.0, table=None):
    """``rotary_embed`` as the parent commit had it (PR 58), word for word: the frequencies
    computed inline from one base."""
    assert table is None
    rd = rotary_dims
    x_rot, x_pass = x[..., :rd], x[..., rd:]
    half = rd // 2
    freqs = jnp.exp(-math.log(base) * jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[:, :, None].astype(jnp.float32) * freqs[None, None, :]  # [B,S,half]
    cos = jnp.cos(angles)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, :, None, :].astype(x.dtype)
    if interleaved:
        x1, x2 = x_rot[..., 0::2], x_rot[..., 1::2]
        r1 = x1 * cos - x2 * sin
        r2 = x2 * cos + x1 * sin
        rotated = jnp.stack([r1, r2], axis=-1).reshape(x_rot.shape)
    else:
        x1, x2 = x_rot[..., :half], x_rot[..., half:]
        rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([rotated, x_pass], axis=-1)


@pytest.mark.parametrize("name,block", [
    ("pythia-1.4b", "rehearse_program"), ("k-exaone-236b-a23b-L5", "rehearse_kinds_program"),
    ("olmoe-1b-7b-L4", "rehearse_program")])
def test_one_rotary_for_all_runs_as_the_parent_bit_for_bit(monkeypatch, name, block):
    """A configuration that states one rotary for all layers (``rotary_by_kind`` None)
    traces the parent's program: ``apply`` and a padded prefill through the cache give the
    logits of the parent's ``rotary_embed`` bit for bit, in bfloat16 as the cells run."""
    one = tfm.TransformerConfig(dtype=jnp.bfloat16, **program_of(_config(name), block))
    assert one.rotary_by_kind is None and tfm.rotary_tables(one) is None
    p = tfm.hold_for_compute(one, tfm.init(one, jax.random.PRNGKey(3)))
    tokens = _tokens(one, (2, 48), seed=9)

    def both():
        full = tfm.apply(one, p, tokens)
        full = full[0] if isinstance(full, tuple) else full
        logits, _ = tfm.apply_with_cache(
            one, p, tokens[:1], tfm.init_cache(one, 1, 48), 0, last_index=40,
            live=jnp.arange(48)[None, :] < 41)
        return np.asarray(full, np.float32), np.asarray(logits, np.float32)

    ours = both()
    monkeypatch.setattr(tfm, "rotary_embed", _parent_rotary_embed)
    theirs = both()
    assert all(np.array_equal(a, b) for a, b in zip(ours, theirs))
