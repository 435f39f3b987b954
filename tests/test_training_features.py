"""Training-feature tail (VERDICT r02 missing #8/#9/#10): Megatron state-dict
factory, progressive layer drop, eigenvalue power iteration, elasticity
runtime enforcement, sparse gradient tensors."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.checkpoint.state_dict_factory import (
    MegatronSDLoader,
    SDLoaderFactory,
    merge_query_key_value,
    split_query_key_value,
)
from deepspeed_tpu.models import transformer as tfm
from deepspeed_tpu.models.transformer import Model, TransformerConfig


# ---------------------------------------------------------------------------
# Megatron state-dict factory (reference runtime/state_dict_factory.py:214)
# ---------------------------------------------------------------------------

def _fake_megatron_sd(num_heads=4, hn=8, h=16, tp=2, version=2.0):
    """Build a TP=1 reference dict then hand-shard it the Megatron way."""
    rng = np.random.default_rng(0)
    full = {
        "transformer.attention.query_key_value.weight": rng.normal(size=(3 * num_heads * hn, h)).astype(np.float32),
        "transformer.attention.query_key_value.bias": rng.normal(size=(3 * num_heads * hn,)).astype(np.float32),
        "transformer.attention.dense.weight": rng.normal(size=(h, num_heads * hn)).astype(np.float32),
        "transformer.mlp.dense_h_to_4h.weight": rng.normal(size=(4 * h, h)).astype(np.float32),
        "transformer.mlp.dense_4h_to_h.weight": rng.normal(size=(h, 4 * h)).astype(np.float32),
        "transformer.ln.weight": rng.normal(size=(h,)).astype(np.float32),
    }
    shards = []
    for r in range(tp):
        sd = {}
        for k, v in full.items():
            if "query_key_value" in k:
                sd[k] = split_query_key_value(v, tp, r, num_heads, version=version)
            elif "dense_h_to_4h" in k:
                sd[k] = np.split(v, tp, axis=0)[r]
            elif "attention.dense" in k or "dense_4h_to_h" in k:
                sd[k] = np.split(v, tp, axis=1)[r]
            else:
                sd[k] = v
        shards.append(sd)
    return full, shards


@pytest.mark.parametrize("version", [0, 2.0])
def test_megatron_merge_roundtrip(version):
    full, shards = _fake_megatron_sd(tp=2, version=version)
    loader = SDLoaderFactory.get_sd_loader(shards, num_heads=4, version=version)
    merged = loader.merge_state_dict()
    for k in full:
        np.testing.assert_allclose(merged[k], full[k], err_msg=k)


def test_megatron_resharding_2_to_4():
    full, shards = _fake_megatron_sd(tp=2)
    loader = MegatronSDLoader(shards, num_heads=4)
    # serve at TP=4: each rank holds 1 head's qkv
    parts = [loader.get_split_state_dict(4, r) for r in range(4)]
    qkv_key = "transformer.attention.query_key_value.weight"
    rebuilt = merge_query_key_value([p[qkv_key] for p in parts], num_heads=4)
    np.testing.assert_allclose(rebuilt, full[qkv_key])
    col = np.concatenate([p["transformer.mlp.dense_h_to_4h.weight"] for p in parts], axis=0)
    np.testing.assert_allclose(col, full["transformer.mlp.dense_h_to_4h.weight"])
    row = np.concatenate([p["transformer.attention.dense.weight"] for p in parts], axis=1)
    np.testing.assert_allclose(row, full["transformer.attention.dense.weight"])


def test_qkv_merge_v0_is_projection_aware():
    # v0 shards are [q;k;v] stacks: naive concat interleaves rank blocks
    full, shards = _fake_megatron_sd(tp=2, version=0)
    k = "transformer.attention.query_key_value.weight"
    naive = np.concatenate([s[k] for s in shards], axis=0)
    assert np.abs(naive - full[k]).max() > 1e-3
    proper = merge_query_key_value([s[k] for s in shards], version=0)
    np.testing.assert_allclose(proper, full[k])


# ---------------------------------------------------------------------------
# Progressive layer drop (reference runtime/progressive_layer_drop.py:5)
# ---------------------------------------------------------------------------

def _pld_cfg(**kw):
    return TransformerConfig(
        vocab_size=128, max_seq_len=32, num_layers=4, num_heads=2, hidden_size=32,
        dtype=jnp.float32, loss_chunk_size=0, pld_enabled=True, pld_theta=0.3,
        pld_gamma=0.01, **kw,
    )


def test_pld_drops_layers_stochastically():
    cfg = _pld_cfg()
    params = tfm.init(cfg, jax.random.PRNGKey(0))
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 128, size=(2, 9)), jnp.int32)
    # inference (no rng): deterministic full depth
    a = tfm.apply(cfg, params, toks)
    b = tfm.apply(cfg, params, toks)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # training at t=0: theta(0)=1 -> keep everything == inference
    t0 = tfm.apply(cfg, params, toks, rng=jax.random.PRNGKey(1), step=0)
    np.testing.assert_allclose(np.asarray(t0), np.asarray(a), rtol=1e-5)
    # large t: theta -> pld_theta, deep layers dropped sometimes
    outs = [
        np.asarray(tfm.apply(cfg, params, toks, rng=jax.random.PRNGKey(i), step=10_000))
        for i in range(8)
    ]
    assert any(np.abs(o - outs[0]).max() > 1e-4 for o in outs[1:])


def test_pld_trains_through_engine():
    cfg = _pld_cfg()
    ds = {
        "train_batch_size": 8, "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 3e-3}},
        "zero_optimization": {"stage": 1},
        "steps_per_print": 10**9, "mesh": {"data": -1},
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=Model(cfg), config=ds)
    b = {"tokens": np.random.default_rng(0).integers(0, 128, size=(8, 33)).astype(np.int32)}
    losses = [float(jax.device_get(engine.train_batch(b)["loss"])) for _ in range(8)]
    assert losses[-1] < losses[0] + 0.1  # stochastic; loose bound
    assert all(np.isfinite(l) for l in losses)


# ---------------------------------------------------------------------------
# Eigenvalue (reference runtime/eigenvalue.py:7)
# ---------------------------------------------------------------------------

def test_eigenvalue_power_iteration_quadratic():
    from deepspeed_tpu.runtime.eigenvalue import Eigenvalue

    # loss = sum_l 0.5 * lambda_l * ||w_l||^2 has per-layer Hessian lambda_l*I
    lambdas = jnp.asarray([1.0, 4.0, 9.0])
    params = {"layers": {"w": jnp.ones((3, 5))}}

    def loss_fn(p):
        return 0.5 * jnp.sum(lambdas[:, None] * jnp.square(p["layers"]["w"]))

    eigs = Eigenvalue(max_iter=30).compute_eigenvalue(loss_fn, params, num_layers=3)
    np.testing.assert_allclose(eigs, [1.0, 4.0, 9.0], rtol=1e-2)


@pytest.mark.slow  # ~6s warm; eigenvalue power iteration on the transformer
# — the small-model eigenvalue tests keep the feature covered warm
def test_eigenvalue_on_transformer_runs():
    from deepspeed_tpu.runtime.eigenvalue import Eigenvalue

    cfg = TransformerConfig(
        vocab_size=64, max_seq_len=16, num_layers=2, num_heads=2, hidden_size=16,
        dtype=jnp.float32, loss_chunk_size=0,
    )
    params = tfm.init(cfg, jax.random.PRNGKey(0))
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 64, size=(2, 17)), jnp.int32)
    eigs = Eigenvalue(max_iter=5).compute_eigenvalue(
        lambda p: tfm.causal_lm_loss(cfg, p, {"tokens": toks}), params, num_layers=2
    )
    assert len(eigs) == 2 and all(np.isfinite(e) and e >= 0 for e in eigs)


# ---------------------------------------------------------------------------
# Elasticity enforcement (reference engine.py:472-481)
# ---------------------------------------------------------------------------

def test_elasticity_enforced_at_engine_init():
    from deepspeed_tpu.elasticity import ElasticityError, compute_elastic_config

    el = {
        "enabled": True, "max_train_batch_size": 32,
        "micro_batch_sizes": [4], "min_gpus": 1, "max_gpus": 64,
        "min_time": 0, "version": 0.1,
    }
    final_batch, valid, micro = compute_elastic_config({"elasticity": el}, world_size=8)
    cfg = TransformerConfig(
        vocab_size=64, max_seq_len=16, num_layers=2, num_heads=2, hidden_size=16,
        dtype=jnp.float32, loss_chunk_size=0,
    )
    base = {
        "train_batch_size": final_batch,
        "train_micro_batch_size_per_gpu": final_batch // 8,
        "optimizer": {"type": "SGD", "params": {"lr": 1e-2}},
        "steps_per_print": 10**9, "mesh": {"data": -1},
        "elasticity": el,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=Model(cfg), config=base)  # compatible: ok
    bad = dict(base, train_batch_size=final_batch * 2,
               train_micro_batch_size_per_gpu=final_batch * 2 // 8)
    with pytest.raises(ElasticityError, match="elastic"):
        deepspeed_tpu.initialize(model=Model(cfg), config=bad)


# ---------------------------------------------------------------------------
# Sparse gradients (reference runtime/sparse_tensor.py:11)
# ---------------------------------------------------------------------------

def test_sparse_tensor_dense_roundtrip():
    from deepspeed_tpu.runtime.sparse_tensor import from_embedding_grad

    ids = jnp.asarray([3, 1, 3], jnp.int32)  # duplicate id accumulates
    grads = jnp.asarray([[1.0, 0.0], [0.0, 2.0], [1.0, 1.0]])
    st = from_embedding_grad(ids, grads, vocab_size=5)
    dense = np.asarray(st.to_dense())
    assert dense.shape == (5, 2)
    np.testing.assert_allclose(dense[3], [2.0, 1.0])
    np.testing.assert_allclose(dense[1], [0.0, 2.0])
    assert dense[[0, 2, 4]].sum() == 0


def test_sparse_all_reduce_over_mesh(mesh8):
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.runtime.sparse_tensor import SparseTensor, sparse_all_reduce

    V, D, N = 8, 4, 2

    def body(ids, vals):
        st = SparseTensor(ids, vals, jnp.asarray(N, jnp.int32), (V, D))
        return sparse_all_reduce(st, "data").to_dense()

    sm = shard_map(
        body, mesh=mesh8, in_specs=(P("data"), P("data")), out_specs=P(),
        check_vma=False,
    )
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, V, size=(8 * N,)), jnp.int32)
    vals = jnp.asarray(rng.normal(size=(8 * N, D)), jnp.float32)
    dense = np.asarray(sm(ids, vals))
    ref = np.zeros((V, D), np.float32)
    np.add.at(ref, np.asarray(ids), np.asarray(vals))
    np.testing.assert_allclose(dense, ref, rtol=1e-5, atol=1e-6)
