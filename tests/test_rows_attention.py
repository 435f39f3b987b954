"""A grouped-query step contracts the cache's rows where they lie (PR 45).

``models/transformer._rows_attention`` against ``xla_attention`` on the layer
sliced out of the stack, through ``_cache_attention``'s own ``attend`` (the
write into the stack, the read, the form chosen from the shapes), and a
grouped-query model's greedy tokens through the serving engine against
``apply``'s. What the form buys is a property of the program compiled for the
chip: ``tests/test_chip_compile_caches.py`` holds that.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import InferenceEngine, Request, ServingEngine
from deepspeed_tpu.models import transformer as tfm
from deepspeed_tpu.models.transformer import Model, TransformerConfig

L, B, SMAX, HKV = 3, 4, 48, 2


@pytest.fixture(autouse=True)
def active_mesh():
    """``cache_heads_merged`` reads the process's active mesh: an earlier test's
    engine may have left one with a tensor axis. -> a setter; None from here on."""
    def set_mesh(mesh):
        tfm._ACTIVE_MESH[0] = mesh

    set_mesh(None)
    yield set_mesh
    set_mesh(None)


@pytest.fixture
def rows_calls(monkeypatch):
    """-> a list that grows by one with every call of ``_rows_attention``."""
    calls, inner = [], tfm._rows_attention
    monkeypatch.setattr(tfm, "_rows_attention", lambda *a, **k: calls.append(1) or inner(*a, **k))
    return calls


def _cfg(group, width, dtype=jnp.float32, **fields):
    return TransformerConfig(**{**dict(
        vocab_size=97, max_seq_len=SMAX, num_layers=L, num_heads=HKV * group, num_kv_heads=HKV,
        qk_head_dim=width, hidden_size=32, pos_emb="rotary", use_bias=False, dtype=dtype,
        loss_chunk_size=0, decode_attn="xla"), **fields})


# what a row of the cache is: two heads of 64 side by side (narrow heads: LFM2), two heads of 128
# (grouped heads at the lanes' width: Falcon-H1), or heads kept apart (two of 32 fill no lane row)
_ROWS = {"rows_of_narrow_heads": 64, "rows_of_wide_heads": 128, "heads": 32}
# (T, pos, write_pos): a scalar ``pos`` is lock-step; row 0 of "idle_row" is parked at Smax;
# "alibi": the additive bias of the key's distance, per query head (``pos_emb="alibi"``)
_BLOCKS = {
    "step_scalar_pos": (1, 17, None),
    "step_row_pos": (1, [0, SMAX - 1, 17, 30], None),
    "step_row_pos_alibi": (1, [0, SMAX - 1, 17, 30], None),
    "block_past_0_scalar_pos": (3, 20, None),
    "block_past_0_scalar_pos_alibi": (3, 20, None),
    "block_past_0_row_pos": (3, [1, SMAX - 3, 17, 30], None),
    "step_idle_row": (1, [0, 9, 17, 30], [SMAX, 9, 17, 30]),
}


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6), (jnp.bfloat16, 2 ** -7)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("block", list(_BLOCKS))
@pytest.mark.parametrize("group", [4, 5, 8])
@pytest.mark.parametrize("rows", list(_ROWS))
def test_a_grouped_block_through_the_cache_is_xla_attention_on_the_sliced_layer(
        rows, group, block, dtype, tol, rows_calls):
    """``attend`` of ``_cache_attention`` on layer 1 of three, the stacks full of
    noise: the new rows land where the reference writes them (an idle row's write
    is dropped), no other element of the stacks moves, and the block's output is
    ``xla_attention``'s grouped form over the layer sliced out and viewed as heads:
    the same products and the same float32 sums (the zeros of the other heads'
    blocks add nothing), so float32 agrees to summation order and bfloat16 to the
    last bit of outputs of size about 1. Merged rows take ``_rows_attention``,
    heads kept apart the grouped form itself."""
    width = _ROWS[rows]
    T, pos, write_pos = _BLOCKS[block]
    alibi = block.endswith("alibi")
    cfg = _cfg(group, width, dtype, **(dict(pos_emb="alibi") if alibi else {}))
    H, merged = cfg.num_heads, rows != "heads"
    assert tfm.cache_heads_merged(cfg) == merged
    assert tfm.cache_rows_step(cfg, T) == merged

    keys = jax.random.split(jax.random.PRNGKey(group * width + T), 5)
    q = jax.random.normal(keys[0], (B, T, H, width), dtype)
    k, v = (jax.random.normal(key, (B, T, HKV, width), dtype) for key in keys[1:3])
    heads = {name: jax.random.normal(key, (L, B, SMAX, HKV, width), dtype)
             for name, key in (("k", keys[3]), ("v", keys[4]))}
    stacks = {name: c.reshape(L, B, SMAX, 1, -1) if merged else c for name, c in heads.items()}
    assert jax.tree.map(jnp.shape, stacks) == jax.tree.map(
        jnp.shape, tfm.init_cache(cfg, B, SMAX))

    kw = {} if write_pos is None else {"write_pos": jnp.asarray(write_pos, jnp.int32)}
    _, attend = tfm._cache_attention(cfg, B, T, SMAX, jnp.asarray(pos, jnp.int32), **kw)
    got, new = attend(q, k, v, stacks, jnp.int32(1), None)
    assert bool(rows_calls) == merged

    want_stacks = {name: np.array(c, np.float32) for name, c in heads.items()}
    at = np.broadcast_to(np.asarray(pos if write_pos is None else write_pos), (B,))
    for b in range(B):
        if at[b] < SMAX:
            for name, block_rows in (("k", k), ("v", v)):
                want_stacks[name][1, b, at[b]:at[b] + T] = np.asarray(block_rows[b], np.float32)
    for name in ("k", "v"):
        assert new[name].shape == stacks[name].shape and new[name].dtype == dtype
        np.testing.assert_array_equal(
            np.asarray(new[name], np.float32).reshape(L, B, SMAX, HKV, width), want_stacks[name])
    bias = None
    if alibi:  # slope x (key position - query position), [B, H, T, Smax]
        q_pos = np.broadcast_to(np.asarray(pos), (B,))[:, None] + np.arange(T)
        dist = np.arange(SMAX)[None, None, :] - q_pos[:, :, None]
        bias = jnp.asarray(np.asarray(tfm.alibi_slopes(H))[None, :, None, None] * dist[:, None],
                           jnp.float32)
    want = tfm.xla_attention(q, *(jnp.asarray(want_stacks[name][1], dtype) for name in ("k", "v")),
                             causal_offset=jnp.asarray(pos, jnp.int32), bias=bias)
    assert got.shape == want.shape == (B, T, H, width) and got.dtype == want.dtype
    gap = np.max(np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32)))
    assert gap <= tol, gap
    if block == "step_idle_row":  # position 0 alone, as the slot's last request left it
        own = np.repeat(np.asarray(heads["v"][1, 0, 0], np.float32), group, axis=0)
        assert np.max(np.abs(np.asarray(got[0, 0], np.float32) - own)) <= tol


def test_a_block_past_the_ridge_views_the_layer_as_heads(rows_calls):
    """The rule by ``T`` (``cache_rows_step``): a block whose query heads x rows pass
    ``ROWS_OPS_PER_BYTE`` operations a byte read (a chunk of a prompt) takes the
    grouped form over the layer viewed as heads, as it did; a block at the ridge
    still contracts the rows; both are the same function."""
    cfg = _cfg(4, 64)
    longest = tfm.ROWS_OPS_PER_BYTE // cfg.num_heads  # 30 rows of 8 query heads
    assert tfm.cache_rows_step(cfg, longest) and not tfm.cache_rows_step(cfg, longest + 1)
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    stacks = {name: jax.random.normal(key, (L, 1, SMAX, 1, HKV * 64))
              for name, key in (("k", keys[3]), ("v", keys[4]))}
    for T in (longest, longest + 1):
        q = jax.random.normal(keys[0], (1, T, cfg.num_heads, 64))
        k, v = (jax.random.normal(key, (1, T, HKV, 64)) for key in keys[1:3])
        _, attend = tfm._cache_attention(cfg, 1, T, SMAX, jnp.int32(5))
        out, new = attend(q, k, v, stacks, jnp.int32(2), None)
        heads = [new[name][2].reshape(1, SMAX, HKV, 64) for name in ("k", "v")]
        np.testing.assert_allclose(out, tfm.xla_attention(q, *heads, causal_offset=5),
                                   rtol=0, atol=2e-6)
    assert len(rows_calls) == 1


_MODELS = {
    # (fields, the cache holds rows, the longest block that contracts them)
    "narrow_heads_grouped": (dict(num_heads=4, num_kv_heads=2, qk_head_dim=64), True, 60),
    "narrow_heads_not_grouped": (dict(num_heads=2, num_kv_heads=0, qk_head_dim=64), True, 0),
    "wide_heads_grouped": (dict(num_heads=4, num_kv_heads=2, qk_head_dim=128), True, 60),
    "wide_heads_not_grouped": (dict(num_heads=2, num_kv_heads=0, qk_head_dim=128), False, 0),
    "grouped_heads_fill_no_lane_row": (dict(num_heads=4, num_kv_heads=1, qk_head_dim=64), False, 0),
    "narrow_heads_pallas_step": (dict(num_heads=2, num_kv_heads=0, qk_head_dim=64,
                                      decode_attn="kernel"), False, 0),
    "grouped_beside_window_layers": (dict(num_heads=4, num_kv_heads=2, qk_head_dim=128,
                                          local_attn_layers=(1, 0, 1), local_attn_window=16),
                                     False, 0),
    "more_query_heads_than_the_ridge": (dict(num_heads=256, num_kv_heads=8, qk_head_dim=128),
                                        False, 0),
}


@pytest.mark.parametrize("name", list(_MODELS))
def test_the_cache_holds_rows_where_a_step_gains_by_them(name):
    """``cache_heads_merged`` / ``cache_rows_step`` read the model's shapes: narrow
    heads merge as they did (PR 42) and contract as rows only where grouped;
    grouped heads of the lanes' width merge now; multi-head attention at that
    width, the Pallas step, a model with rings and heads that fill no lane row
    keep [L, B, Smax, heads, width], as does a model whose query heads alone pass
    the ridge."""
    fields, merged, longest = _MODELS[name]
    cfg = _cfg(1, 64, **fields)
    assert tfm.cache_heads_merged(cfg) == merged
    heads = (1, cfg.kv_heads * cfg.head_dim) if merged else (cfg.kv_heads, cfg.head_dim)
    assert tfm.cache_layout(cfg)["k"] == tfm.cache_layout(cfg)["v"] == heads
    tokens = tfm.cache_layers(cfg)["tokens"]
    assert jax.eval_shape(lambda: tfm.init_cache(cfg, 2, 8))["k"].shape == (tokens, 2, 8) + heads
    assert tfm.cache_rows_step(cfg, max(longest, 1)) == bool(longest)
    assert not tfm.cache_rows_step(cfg, longest + 1)
    assert tfm.cache_step_form(cfg) == ("dense+ring" if cfg.window_layers else "dense")  # as before


def test_grouped_heads_a_tensor_axis_would_shard_stay_heads(active_mesh):
    """A row is one 'head' and would replicate over the mesh's tensor axis: grouped
    heads of the lanes' width that the active mesh shards stay heads (each shard
    its own K/V heads, as before); narrow heads merge there too, as since PR 42;
    an axis that does not divide the K/V heads shards nothing, so rows again."""
    from deepspeed_tpu.comm.mesh import MeshConfig, build_mesh

    wide, narrow = (_cfg(4, width) for width in (128, 64))
    odd = _cfg(4, 128, num_heads=12, num_kv_heads=3)
    assert tfm.cache_heads_merged(wide) and tfm.cache_heads_merged(odd)
    active_mesh(build_mesh(MeshConfig(model=2, data=-1)))
    assert not tfm.cache_heads_merged(wide) and not tfm.cache_rows_step(wide)
    assert tfm.cache_layout(wide)["k"] == (HKV, 128)
    assert tfm.cache_heads_merged(narrow) and tfm.cache_heads_merged(odd)
    active_mesh(build_mesh(MeshConfig(data=-1)))
    assert tfm.cache_heads_merged(wide)


def _greedy_by_apply(forward, params, prompt, n):
    """``n`` greedy tokens after ``prompt`` by ``forward`` (``apply``, jitted) on the
    whole sequence, padded to one length for one compile: causality hides the tail."""
    tokens = np.zeros((1, 64), np.int32)
    tokens[0, :len(prompt)] = prompt
    for at in range(len(prompt), len(prompt) + n):
        tokens[0, at] = int(jnp.argmax(forward(params, tokens)[0, at - 1]))
    return tokens[0, len(prompt):len(prompt) + n]


@pytest.mark.parametrize("serving", ["one_shot_prefill", "chunked_prefill", "speculation"])
@pytest.mark.parametrize("width", [64, 128], ids=["narrow_heads", "wide_heads"])
def test_a_grouped_model_serves_the_tokens_apply_computes(width, serving):
    """8 query heads over 2 K/V heads through the serving engine's own programs
    (``SlotWorker``: bucketed prefill into a local cache, the decode step over the
    slot cache's rows at per-row positions with idle rows parked at Smax; a chunk
    of 16 tokens entering past position 0, 128 operations a byte: rows; a verify
    block of drafts) against greedy decoding by ``apply``, which never sees a
    cache: every request's tokens are equal."""
    cfg = _cfg(4, width, num_layers=2, max_seq_len=128)
    eng = InferenceEngine(model=Model(cfg), config={"dtype": "fp32"})
    config = {"one_shot_prefill": {},
              "chunked_prefill": {"chunked_prefill": {"enabled": True, "chunk_size": 16}},
              "speculation": {"speculation": {"enabled": True}}}[serving]
    srv = ServingEngine(eng, n_slots=3, max_seq_len=128, config=config)
    assert srv.worker._cache["k"].shape == (2, 3, 128, 1, HKV * width)
    rng = np.random.default_rng(width)
    prompts = [rng.integers(0, 97, size=s).astype(np.int32) for s in (5, 21, 37, 9)]
    res = srv.serve([Request(uid=i, prompt=p, max_new_tokens=6) for i, p in enumerate(prompts)])
    forward = jax.jit(lambda p, t: tfm.apply(cfg, p, t))
    for i, p in enumerate(prompts):
        np.testing.assert_array_equal(res[i].tokens, _greedy_by_apply(forward, eng.params, p, 6))
    counts = srv.compile_counts()
    assert counts["decode"] == 1
    ran = {"one_shot_prefill": "prefill", "chunked_prefill": "chunk_prefill",
           "speculation": "verify"}[serving]
    assert counts[ran], counts  # the block past position 0 (a chunk, a verify block) DID run
