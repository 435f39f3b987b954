"""Decode-attention Pallas kernel + sampler (VERDICT r02 ask #3).

Reference kernel being matched: softmax_context_* — single-token attention
over the valid KV-cache prefix (csrc/transformer/inference/csrc/
pt_binding.cpp:1237-1283). Tests run the kernel in interpreter mode on the
CPU mesh and compare against the dense XLA cached_attention.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.sampling import (
    SamplerConfig,
    apply_top_k,
    apply_top_p,
    sample_logits,
    update_seen,
)
from deepspeed_tpu.models.transformer import (
    Model,
    TransformerConfig,
    xla_attention,
)
from deepspeed_tpu.ops.pallas.decode_attention import decode_attention


def _qkv(B=2, H=4, D=32, Smax=256, seed=0):
    r = jax.random.PRNGKey(seed)
    k1, k2, k3 = jax.random.split(r, 3)
    q = jax.random.normal(k1, (B, H, D), jnp.float32)
    kc = jax.random.normal(k2, (B, Smax, H, D), jnp.float32)
    vc = jax.random.normal(k3, (B, Smax, H, D), jnp.float32)
    return q, kc, vc


@pytest.mark.parametrize("pos", [0, 3, 127, 128, 255])
def test_decode_attention_matches_dense(pos):
    q, kc, vc = _qkv()
    out = decode_attention(q, kc, vc, pos, block_k=128)
    ref = xla_attention(q[:, None], kc, vc, causal_offset=pos)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_decode_attention_per_row_pos():
    q, kc, vc = _qkv(B=3)
    pos = jnp.asarray([0, 100, 255], jnp.int32)
    out = decode_attention(q, kc, vc, pos, block_k=64)
    for b in range(3):
        ref = xla_attention(q[b : b + 1, None], kc[b : b + 1], vc[b : b + 1],
                            causal_offset=int(pos[b]))[:, 0]
        np.testing.assert_allclose(np.asarray(out[b]), np.asarray(ref[0]), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("layer", [0, 2])
def test_decode_attention_reads_a_layer_of_the_stacked_cache(layer):
    """``layer=`` reads layer l of the [L, B, Smax, H, D] stacks in place,
    bit-identical to the kernel on that layer sliced out (traced index: one
    program for every layer, as the model's layer scan calls it)."""
    q, kc, vc = _qkv(B=3)
    pos = jnp.asarray([0, 100, 255], jnp.int32)
    stack = lambda c: jnp.stack([c, c[::-1], c * 0.5])
    ks, vs = stack(kc), stack(vc)
    out = jax.jit(lambda l: decode_attention(q, ks, vs, pos, layer=l, block_k=64))(layer)
    ref = decode_attention(q, ks[layer], vs[layer], pos, block_k=64)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_decode_in_model_matches_xla_path():
    cfg_k = TransformerConfig(
        vocab_size=97, max_seq_len=128, num_layers=2, num_heads=4, hidden_size=32,
        dtype=jnp.float32, loss_chunk_size=0, decode_attn="kernel", pos_emb="rotary",
    )
    cfg_x = cfg_k.replace(decode_attn="xla")
    from deepspeed_tpu.models import transformer as tfm

    params = tfm.init(cfg_k, jax.random.PRNGKey(0))
    cache_k = tfm.init_cache(cfg_k, 2, 128)
    cache_x = tfm.init_cache(cfg_x, 2, 128)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, 97)
    lk, cache_k = tfm.apply_with_cache(cfg_k, params, prompt, cache_k, 0, last_only=True)
    lx, cache_x = tfm.apply_with_cache(cfg_x, params, prompt, cache_x, 0, last_only=True)
    np.testing.assert_allclose(np.asarray(lk), np.asarray(lx), rtol=1e-4, atol=1e-4)
    tok = jnp.argmax(lk[:, -1], axis=-1).astype(jnp.int32)[:, None]
    # decode step: kernel vs dense
    lk1, _ = tfm.apply_with_cache(cfg_k, params, tok, cache_k, 17)
    lx1, _ = tfm.apply_with_cache(cfg_x, params, tok, cache_x, 17)
    np.testing.assert_allclose(np.asarray(lk1), np.asarray(lx1), rtol=1e-4, atol=1e-4)


def test_top_k():
    logits = jnp.asarray([[1.0, 5.0, 3.0, 2.0]])
    out = apply_top_k(logits, 2)
    assert np.isneginf(np.asarray(out)[0, 0]) or out[0, 0] < -1e29
    assert out[0, 1] == 5.0 and out[0, 2] == 3.0
    assert out[0, 3] < -1e29


def test_top_p():
    # probs ~ [0.643, 0.236, 0.087, 0.032]; top_p=0.6 keeps only the first
    logits = jnp.asarray([[4.0, 3.0, 2.0, 1.0]])
    out = apply_top_p(logits, 0.6)
    assert out[0, 0] == 4.0
    assert (np.asarray(out[0, 1:]) < -1e29).all()
    # top_p=0.7: cumulative-before for 2nd token is 0.643 < 0.7 -> kept
    out = apply_top_p(logits, 0.7)
    assert out[0, 1] == 3.0
    assert (np.asarray(out[0, 2:]) < -1e29).all()


def test_repetition_penalty_and_greedy():
    logits = jnp.asarray([[2.0, 1.9, -1.0]])
    seen = update_seen(jnp.zeros((1, 3), jnp.bool_), jnp.asarray([[0]]))
    cfg = SamplerConfig(temperature=0.0, repetition_penalty=2.0)
    tok = sample_logits(logits, jax.random.PRNGKey(0), cfg, seen=seen)
    # token 0 penalized 2.0 -> 1.0; argmax moves to token 1
    assert int(tok[0]) == 1


def test_sampled_generation_runs():
    cfg = TransformerConfig(
        vocab_size=97, max_seq_len=128, num_layers=2, num_heads=4, hidden_size=32,
        dtype=jnp.float32, loss_chunk_size=0,
    )
    from deepspeed_tpu.inference.engine import InferenceEngine

    eng = InferenceEngine(model=Model(cfg), config={"dtype": "fp32"})
    prompt = np.random.default_rng(0).integers(0, 97, size=(2, 9)).astype(np.int32)
    out = eng.generate(prompt, max_new_tokens=6, temperature=0.8, top_k=20,
                       top_p=0.9, repetition_penalty=1.2)
    assert out.shape == (2, 6)
    assert (out >= 0).all() and (out < 97).all()
