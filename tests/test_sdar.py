"""SDAR-30B-A3B-Chat's twin as a model: ``apply`` under the block-causal mask against the
plain reference at every row, the causal program unchanged at block length 1, the dense and
the flash form of a prefill, the sampler's helpers, the reference's own ``generate``, and
what is refused by name."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sdar_cases import (  # noqa: F401,I001 -- first: puts the repo's root on sys.path; fixtures
    ROOT, WHOLE, TOL, BLOCK, program, reference, cfg, params, _tokens, program_at,
    causal_inside_a_block)

from chipbench import parity  # noqa: E402
from chipbench.references import load_reference  # noqa: E402
from deepspeed_tpu.inference import sampling  # noqa: E402
from deepspeed_tpu.models import transformer as tfm  # noqa: E402
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention  # noqa: E402

SEQ = 96


def _model(block, dtype=jnp.float32):
    prog = program_at(block)
    cfg = tfm.TransformerConfig(dtype=dtype, **prog)
    return prog, cfg, parity._seeded_params(tfm, cfg)


@pytest.mark.parametrize("block", [1, 2, 4])
def test_apply_is_the_reference_at_every_row(block):
    prog, cfg, params = _model(block)
    tokens = _tokens(cfg, (2, SEQ), block)
    got = np.asarray(tfm.apply(cfg, params, tokens))
    want = np.stack([load_reference(prog).logits_at(prog, params, t, np.arange(SEQ), fetch=WHOLE)
                     for t in tokens])
    assert np.abs(got - want).max() <= TOL
    # ... and a block's rows see their block: the causal reference is far from it
    if block > 1:
        causal = load_reference(program_at(1)).logits_at(program_at(1), params, tokens[0],
                                                         np.arange(SEQ), fetch=WHOLE)
        assert np.abs(got[0] - causal).max() > 100 * TOL


def test_block_length_one_is_todays_causal_program_to_the_bit():
    prog, cfg, params = _model(1)
    stated = cfg.replace(attn_block_length=1)
    tokens = _tokens(cfg, (2, SEQ), 7)
    assert str(jax.make_jaxpr(lambda p, t: tfm.apply(cfg, p, t))(params, tokens)) == \
        str(jax.make_jaxpr(lambda p, t: tfm.apply(stated, p, t))(params, tokens))
    np.testing.assert_array_equal(np.asarray(tfm.apply(cfg, params, tokens)),
                                  np.asarray(tfm.apply(stated, params, tokens)))


@pytest.mark.parametrize("block", [2, 4])
def test_dense_and_flash_prefill_forms_agree_with_the_reference(block, monkeypatch):
    """A 256-row prefill that fills its cache, densely and (the rule's threshold lowered for
    the length of the test) through the interpreted flash kernel, at three live rows."""
    prog, cfg, params = _model(block)
    tokens = _tokens(cfg, (1, 256), 11)
    rows = np.array([255, 100, 3])
    want = load_reference(prog).logits_at(prog, params, tokens[0], rows, fetch=WHOLE)
    for form, limit in (("dense", tfm.DENSE_SCORE_BYTES), ("flash", 0)):
        monkeypatch.setattr(tfm, "DENSE_SCORE_BYTES", limit)
        assert tfm.cache_attention_form(cfg.num_heads, 1, 256, 256) == form
        cache = tfm.init_cache(cfg, 1, 256, dtype=jnp.float32)
        got = np.stack([np.asarray(tfm.apply_with_cache(
            cfg, params, tokens, cache, 0, last_index=int(r))[0][0, 0]) for r in rows])
        assert np.abs(got - want).max() <= TOL, form


@pytest.mark.parametrize("block", [4, 16])
def test_flash_kernel_masks_by_block(block):
    """The kernel alone against the XLA form, rows that several tiles and sub-tiles cut."""
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (1, 384, 2, 64), jnp.float32)
               for i in range(3))
    got = flash_attention(q, k, v, causal=True, mask_block=block, block_q=128, block_k=128)
    want = tfm.xla_attention(q, k, v, block=block)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() <= 2e-5
    assert np.abs(np.asarray(got) - np.asarray(tfm.xla_attention(q, k, v))).max() > 1e-2


def test_bfloat16_compute_fails_the_tolerance():
    prog, cfg, params = _model(BLOCK, jnp.bfloat16)
    tokens = _tokens(cfg, (1, SEQ), 3)
    got = np.asarray(tfm.apply(cfg, params, tokens), np.float32)
    want = load_reference(prog).logits_at(prog, params, tokens[0], np.arange(SEQ), fetch=WHOLE)
    assert np.abs(got[0] - want).max() > 10 * TOL


def test_the_causal_mask_inside_a_block_fails_the_tolerance(program, reference, cfg, params):
    tokens = _tokens(cfg, (1, SEQ), 5)
    want = reference.logits_at(program, params, tokens[0], np.arange(SEQ), fetch=WHOLE)
    with causal_inside_a_block():
        got = np.asarray(tfm.apply(cfg, params, tokens))
    assert np.abs(got[0] - want).max() > 100 * TOL


# -- refused by name -------------------------------------------------------------------------

@pytest.mark.parametrize("what,fields", [
    ("window layers", dict(local_attn_window=16, local_attn_layers=[1, 0, 1])),
    ("state-space mixer", dict(ssm_state_size=16, ssm_heads=4, ssm_head_dim=16, num_kv_heads=0,
                               qk_norm=False)),
    ("layer_operators", dict(layer_operators=["conv", "attn", "conv"], conv_kernel=3)),
    ("layer_passes", dict(layer_passes=2, moe_every=0, moe_routing="gshard", num_experts=1,
                          moe_top_k=1, moe_norm_topk_prob=False)),
    ("alibi", dict(pos_emb="alibi", qk_norm=False)),
    ("decode_attn='kernel'", dict(decode_attn="kernel", num_kv_heads=0)),
    ("attn_impl", dict(attn_impl="ring", num_kv_heads=0)),
    ("causal=False", dict(causal=False)),
])
def test_what_no_code_attends_under_the_block_mask_is_refused_by_name(program, what, fields):
    with pytest.raises(NotImplementedError) as e:
        tfm.TransformerConfig(**{**program, **fields})
    assert "attn_block_length" in str(e.value) or what.split("=")[0] in str(e.value)


@pytest.mark.parametrize("fields,words", [
    (dict(attn_block_length=3), "power of two"),
    (dict(attn_block_length=256), "power of two"),
    (dict(mask_token_id=-1), "mask_token_id"),
    (dict(mask_token_id=768), "mask_token_id"),
])
def test_a_block_length_or_a_mask_token_out_of_range_is_refused(program, fields, words):
    with pytest.raises(ValueError, match=words):
        tfm.TransformerConfig(**{**program, **fields})


def test_latent_attention_under_the_block_mask_is_refused(program):
    fields = dict(kv_lora_rank=16, qk_rope_head_dim=8, qk_head_dim=24, v_head_dim=16,
                  num_kv_heads=0, qk_norm=False)
    with pytest.raises(NotImplementedError):
        tfm.TransformerConfig(**{**program, **fields})


def test_the_training_objective_is_refused_by_name(program, reference, cfg, params):
    tokens = _tokens(cfg, (2, 33), 1)
    with pytest.raises(NotImplementedError, match="noise"):
        tfm.causal_lm_loss(cfg, params, {"tokens": tokens})
    with pytest.raises(NotImplementedError, match="noise schedule"):
        reference.lm_loss(program, params, tokens, fetch=WHOLE)
    q = jnp.ones((1, 128, 2, 64))
    with pytest.raises(NotImplementedError, match="mask_block"):
        jax.grad(lambda q: flash_attention(q, q, q, mask_block=4).sum())(q)
    with pytest.raises(NotImplementedError, match="pipeline"):
        tfm.refuse_in_pipeline(cfg)


# -- the sampler's helpers ---------------------------------------------------------------------

@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_sample_with_confidence_is_the_softmax_of_the_token_chosen(temperature):
    logits = jax.random.normal(jax.random.PRNGKey(0), (6, 50)) * 3
    t = jnp.full((6,), temperature)
    tok, conf = sampling.sample_with_confidence(logits, jax.random.PRNGKey(1), t,
                                                jnp.zeros((6,), jnp.int32), jnp.ones((6,)))
    want = sampling.sample_logits_vector(logits, jax.random.PRNGKey(1), t,
                                         jnp.zeros((6,), jnp.int32), jnp.ones((6,)))
    np.testing.assert_array_equal(tok, want)
    probs = np.asarray(jax.nn.softmax(logits, axis=-1))
    np.testing.assert_allclose(conf, probs[np.arange(6), np.asarray(tok)], rtol=1e-5)


@pytest.mark.parametrize("count,threshold,want", [
    (1, np.inf, [[0, 0, 1, 0], [0, 1, 0, 0], [0, 0, 0, 0]]),
    (2, np.inf, [[1, 0, 1, 0], [0, 1, 0, 1], [0, 0, 0, 0]]),
    (4, np.inf, [[1, 0, 1, 1], [0, 1, 0, 1], [0, 0, 0, 0]]),
    (0, np.inf, [[0, 0, 0, 0], [0, 0, 0, 0], [0, 0, 0, 0]]),
    (1, 0.25, [[1, 0, 1, 0], [0, 1, 0, 0], [0, 0, 0, 0]]),
    (0, 0.05, [[1, 0, 1, 1], [0, 1, 0, 1], [0, 0, 0, 0]]),
])
def test_reveal_rows_takes_the_most_confident_masked_rows(count, threshold, want):
    conf = np.array([[0.3, 0.9, 0.5, 0.1], [0.9, 0.2, 0.8, 0.2], [0.5, 0.5, 0.5, 0.5]], np.float32)
    masked = np.array([[1, 0, 1, 1], [0, 1, 0, 1], [0, 0, 0, 0]], bool)
    got = sampling.reveal_rows(conf, masked, np.full((3,), count, np.int32),
                               np.full((3,), threshold, np.float32))
    np.testing.assert_array_equal(np.asarray(got), np.array(want, bool))


@pytest.mark.parametrize("steps,want", [(4, [1, 1, 1, 1]), (2, [2, 2]), (1, [4]), (3, [1, 1, 2])])
def test_the_references_schedule_reveals_a_block_in_its_passes(reference, steps, want):
    left, got = 4, []
    for p in range(steps):
        got.append(reference.reveal_count(4, steps, p, left))
        left -= got[-1]
    assert got == want and left == 0


@pytest.mark.parametrize("steps,strategy", [(4, "low_confidence_static"), (2, "low_confidence_static"),
                                            (4, "low_confidence_dynamic")])
def test_generate_reveals_by_confidence_and_keeps_the_prompts_tail(program, reference, cfg, params,
                                                                   steps, strategy):
    """A prompt of P mod B = 3 keeps its three last tokens in the first block; every pass
    reveals the most confident masked rows; the mask token's own id in a prompt is no mask."""
    prompt = _tokens(cfg, (11,), 2)
    prompt[9] = program["mask_token_id"]
    out = reference.generate(program, params, prompt, 7, fetch=WHOLE, denoising_steps=steps,
                             strategy=strategy, threshold=0.004)
    assert len(out["tokens"]) == 7
    first = out["passes"][0]
    assert first["start"] == 8 and first["masked"] == [11]
    np.testing.assert_array_equal(first["sequence"][:11], prompt)
    for p in out["passes"]:
        if p["commit"]:
            assert not p["masked"] and not p["revealed"]
            continue
        conf = p["confidence"]
        assert set(p["revealed"]) <= set(p["masked"])
        hidden = [q for q in p["masked"] if q not in p["revealed"]]
        assert all(conf[r - p["start"]] >= conf[h - p["start"]]
                   for r in p["revealed"] for h in hidden)
    with pytest.raises(ValueError, match="denoising_steps"):
        reference.generate(program, params, prompt, 3, fetch=WHOLE, denoising_steps=5)
