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
from deepspeed_tpu.ops.pallas import decode_attention as da
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
    """Scalar ``pos`` (every row alike) round the block's edges: the rule gives a
    cache of 256 positions blocks of 128 (block - 1, block, Smax - 1, 0)."""
    q, kc, vc = _qkv()
    assert da.block_rows(256, 4 * 32 * 4) == 128
    out = decode_attention(q, kc, vc, pos)
    ref = xla_attention(q[:, None], kc, vc, causal_offset=pos)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def _against_dense(q, kc, vc, pos, out, **kw):
    for b in range(q.shape[0]):
        ref = xla_attention(q[b : b + 1, None], kc[b : b + 1], vc[b : b + 1],
                            causal_offset=int(pos[b]), **kw)[:, 0]
        np.testing.assert_allclose(np.asarray(out[b]), np.asarray(ref[0]), rtol=1e-5, atol=1e-5)


# the walk's edges: rows x cache length -> blocks by the rule (4 heads of 32 in float32)
WALKS = {
    "per_row": ([0, 100, 255], 256),                       # the case this file had: blocks of 128
    "block_edges": ([127, 128, 129, 255, 0], 256),         # block - 1, block, block + 1, Smax - 1, 0
    "every_row_full": ([511, 511, 511], 512),              # the longest grid
    "every_row_at_0": ([0, 0, 0, 0], 512),                 # one block a row: the shortest
    # one-block and many-block rows with an inactive row (the engine parks it at 0) between them
    "mixed_with_an_inactive_row": ([5, 700, 0, 1023, 130, 127], 1024),
    "smax_not_a_multiple_of_the_block": ([0, 191, 575, 200], 576),  # 512 B a row: 1,024 -> 144 -> 128 -> 64 | 576
    "smax_not_a_multiple_of_128": ([0, 95, 150, 319], 320),         # the rule's 128 halves to 64
}


@pytest.mark.parametrize("case", list(WALKS))
def test_decode_attention_per_row_pos(case):
    pos, smax = WALKS[case]
    q, kc, vc = _qkv(B=len(pos), Smax=smax)
    pos = jnp.asarray(pos, jnp.int32)
    assert smax % da.block_rows(smax, 4 * 32 * 4) == 0
    _against_dense(q, kc, vc, pos, decode_attention(q, kc, vc, pos))


@pytest.mark.parametrize("smax,row_bytes,block", [
    (1024, 4096, 128),   # ouro-2.6b-L12.serve-reason: 16 heads of 128 in bfloat16, 1 MiB of K and V
    (2048, 4096, 128),   # olmoe-1b-7b-L4.serve-doc: the same row, the same block
    (2048, 1536, 256),   # 12 heads of 64 (GPT-2 125M, chip_smoke.py): a narrower row, a longer block
    (8192, 256, 2048),   # the bytes would allow 2,048; so does a quarter of the cache
    (2048, 256, 512),    # ... and here the quarter bounds it
    (256, 512, 128),     # a short cache: not under 128 positions ...
    (64, 512, 64),       # ... nor over the cache
    (384, 512, 128),     # halved until it divides the cache
    (320, 4096, 64),
])
def test_block_comes_from_the_shape(smax, row_bytes, block):
    """The cases that passed ``block_k`` are cases of the rule now: the block is a
    function of the cache's length and a cached position's bytes alone."""
    assert da.block_rows(smax, row_bytes) == block
    assert 2 * block * row_bytes <= da.FETCH_BYTES and smax % block == 0


def test_cache_with_no_block_divisor_is_refused_by_name():
    assert da.block_rows(264, 4096) == 8  # 8 x 33: slow, not wrong
    q, kc, vc = _qkv(Smax=250)
    with pytest.raises(ValueError, match="no power-of-two block divisor"):
        decode_attention(q, kc, vc, 5)


@pytest.mark.parametrize("case", ["mixed_with_an_inactive_row", "every_row_at_0", "every_row_full"])
def test_walk_visits_what_the_host_counts(case):
    """The work list holds every live block once, in row order, and the grid is as
    long as the list: the blocks it visits are the host's ``kv_rows_fetched`` / block
    (the ``.../decode`` span's attribute) for the same positions."""
    from deepspeed_tpu.models import transformer as tfm

    pos, smax = WALKS[case]
    block = da.block_rows(smax, 4 * 32 * 4)
    walk = da.decode_walk(jnp.asarray(pos, jnp.int32), len(pos), smax, block)
    n = int(walk.n_live)
    assert n * block == tfm.kv_rows_fetched(np.asarray(pos), block)
    visited = list(zip(np.asarray(walk.rows).tolist(), np.asarray(walk.blocks).tolist()))
    assert visited[:n] == [(b, j) for b, p in enumerate(pos) for j in range(p // block + 1)]
    assert len(visited) == len(pos) * (smax // block)  # room for every row full


def test_decode_attention_takes_a_walk_built_outside():
    """A model builds the list once a step and hands it to every layer's call:
    bit-identical to the call that builds its own."""
    pos, smax = WALKS["mixed_with_an_inactive_row"]
    q, kc, vc = _qkv(B=len(pos), Smax=smax)
    pos = jnp.asarray(pos, jnp.int32)
    walk = da.decode_walk(pos, len(pos), smax, da.block_rows(smax, 4 * 32 * 4))
    np.testing.assert_array_equal(np.asarray(decode_attention(q, kc, vc, pos, walk=walk)),
                                  np.asarray(decode_attention(q, kc, vc, pos)))
    # another block than the rule's is the walk's to state: the kernel follows it
    coarse = da.decode_walk(pos, len(pos), smax, 512)
    _against_dense(q, kc, vc, pos, decode_attention(q, kc, vc, pos, walk=coarse))


def test_decode_attention_alibi_in_kernel():
    from deepspeed_tpu.models.transformer import alibi_slopes

    pos, smax = WALKS["mixed_with_an_inactive_row"]
    q, kc, vc = _qkv(B=len(pos), Smax=smax)
    slopes = alibi_slopes(4)
    out = decode_attention(q, kc, vc, jnp.asarray(pos, jnp.int32), alibi_slopes=slopes)
    for b, p in enumerate(pos):
        bias = (slopes[:, None] * (jnp.arange(smax)[None, :] - p))[None, :, None, :]
        ref = xla_attention(q[b : b + 1, None], kc[b : b + 1], vc[b : b + 1], causal_offset=p,
                            bias=bias.astype(jnp.float32))[:, 0]
        np.testing.assert_allclose(np.asarray(out[b]), np.asarray(ref[0]), rtol=1e-5, atol=1e-5)


def test_decode_attention_bfloat16_cache_keeps_the_probabilities():
    """A bfloat16 cache: products exact, statistics and accumulator in float32, the
    probabilities as two bfloat16 terms, so the kernel lies CLOSER to the float32
    definition than the definition run in bfloat16 does (it rounds scores and
    probabilities to 8 bits), by a wide margin."""
    pos, smax = WALKS["mixed_with_an_inactive_row"]
    q, kc, vc = (x.astype(jnp.bfloat16) for x in _qkv(B=len(pos), Smax=smax, H=8, D=128))
    pos = jnp.asarray(pos, jnp.int32)
    exact = xla_attention(*(x.astype(jnp.float32) for x in (q[:, None], kc, vc)),
                          causal_offset=pos)[:, 0]
    far = lambda x: float(jnp.max(jnp.abs(x.astype(jnp.float32) - exact)))
    kernel = decode_attention(q.astype(jnp.float32), kc, vc, pos)  # float32 out: no last rounding
    assert far(kernel) < 2e-5
    assert far(xla_attention(q[:, None], kc, vc, causal_offset=pos)[:, 0]) > 50 * far(kernel)


def test_grouped_heads_are_refused_by_name():
    q, kc, vc = _qkv()
    with pytest.raises(NotImplementedError, match="query heads over"):
        decode_attention(q, kc[:, :, :2], vc[:, :, :2], 5)


@pytest.mark.parametrize("layer", [0, 2])
def test_decode_attention_reads_a_layer_of_the_stacked_cache(layer):
    """``layer=`` reads layer l of the [L, B, Smax, H, D] stacks in place,
    bit-identical to the kernel on that layer sliced out (traced index: one
    program for every layer, as the model's layer scan calls it)."""
    q, kc, vc = _qkv(B=3)
    pos = jnp.asarray([0, 100, 255], jnp.int32)
    stack = lambda c: jnp.stack([c, c[::-1], c * 0.5])
    ks, vs = stack(kc), stack(vc)
    out = jax.jit(lambda l: decode_attention(q, ks, vs, pos, layer=l))(layer)
    ref = decode_attention(q, ks[layer], vs[layer], pos)
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


@pytest.mark.parametrize("decode_attn", ["kernel", "xla"])
def test_decode_span_says_what_the_walk_fetched(decode_attn):
    """``kv_rows_fetched`` beside ``cached_tokens`` on the ``.../decode`` span: whole
    blocks of the kernel's own rule for the ACTIVE rows (the idle slots' one block
    each is not the requests'), and nothing where a step goes round the kernel."""
    import time

    from deepspeed_tpu.inference import serving
    from deepspeed_tpu.launcher.serving_worker import build_serving_engine
    from deepspeed_tpu.models import transformer as tfm
    from deepspeed_tpu.telemetry import tracing

    srv = build_serving_engine({
        "model": {"vocab_size": 64, "num_layers": 2, "num_heads": 2, "hidden_size": 16,
                  "max_seq_len": 512, "decode_attn": decode_attn, "pos_emb": "rotary",
                  "dtype": "float32"},
        "engine_dtype": "fp32", "serving": {"n_slots": 3, "max_seq_len": 512, "seed": 0}})
    block = srv.worker.kv_block
    assert block == (128 if decode_attn == "kernel" else None)
    t0 = time.perf_counter()
    srv.serve([serving.Request(uid=i, prompt=np.arange(n, dtype=np.int32), max_new_tokens=3)
               for i, n in enumerate((9, 127))])
    decodes = [sp for sp in tracing.spans(t0) if sp.name == "decode"]
    assert decodes
    if block is None:
        assert not any("kv_rows_fetched" in sp.attrs for sp in decodes)
        return
    both = [sp for sp in decodes if sp.attrs["n_active"] == 2]
    assert [(sp.attrs["cached_tokens"], sp.attrs["kv_rows_fetched"]) for sp in both] == [
        (10 + 128, 128 + 128), (11 + 129, 128 + 256)]  # position 127 ends block 0, 128 opens block 1
    assert both[0].attrs["kv_rows_fetched"] == tfm.kv_rows_fetched(np.array([9, 127]), block)


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
