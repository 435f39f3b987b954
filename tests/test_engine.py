"""Engine integration tests on the 8-device CPU mesh — the analogue of the
reference's tests/unit/test_fp16.py + test_zero.py stage×offload matrix."""

import os

import jax
import numpy as np
import pytest

import deepspeed_tpu
from simple_model import base_config, random_tokens, tiny_transformer

jnp = jax.numpy


def _make_engine(zero_stage=0, dtype=None, mesh_over=None, **cfg_over):
    model = tiny_transformer()
    cfg = base_config(**cfg_over)
    cfg["zero_optimization"] = {"stage": zero_stage}
    cfg["mesh"] = mesh_over or {"data": -1}
    if dtype == "bf16":
        cfg["bf16"] = {"enabled": True}
    elif dtype == "fp16":
        cfg["fp16"] = {"enabled": True}
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=cfg)
    return engine


@pytest.mark.parametrize("stage", [0, 1, 2, 3])
def test_zero_stage_trains(stage):
    engine = _make_engine(zero_stage=stage)
    batch = random_tokens(16)
    losses = [float(engine.train_batch(batch)["loss"]) for _ in range(5)]
    assert losses[-1] < losses[0], f"stage {stage}: no learning: {losses}"
    assert engine.global_steps == 5
    # the state the engine builds must already look like the state the step
    # returns (placed on the mesh): a second call must not retrace
    assert engine._train_step._cache_size() == 1


def test_dropped_engine_frees_its_state():
    """jax's jit caches keep the compiled step function alive; the step must
    not close over the engine, or a dropped engine's whole device state
    (params + optimizer, 1.5 GB at 125M) stays allocated for the process's
    life — what chip_smoke's four-chip phase found on device 0."""
    import gc
    import weakref

    engine = _make_engine(zero_stage=1)
    engine.train_batch(random_tokens(16))
    leaf = weakref.ref(jax.tree.leaves(engine.state["params"])[0])
    del engine
    gc.collect()
    assert leaf() is None


@pytest.mark.parametrize("stage", [1, 3])
def test_zero_with_fsdp_axis(stage):
    engine = _make_engine(zero_stage=stage, mesh_over={"data": 2, "fsdp": 4})
    batch = random_tokens(16)
    losses = [float(engine.train_batch(batch)["loss"]) for _ in range(3)]
    assert losses[-1] < losses[0]


def test_zero3_param_sharding_applied():
    engine = _make_engine(zero_stage=3, mesh_over={"data": 1, "fsdp": 8})
    wi_sharding = engine.state["params"]["layers"]["wi"].sharding
    # embed dim (64) sharded over fsdp=8 for stage 3
    assert "fsdp" in str(wi_sharding.spec)


def test_zero12_params_replicated_opt_sharded():
    engine = _make_engine(zero_stage=2)
    p_spec = str(engine.state["params"]["layers"]["wi"].sharding.spec)
    m_spec = str(engine.state["opt"]["m"]["layers"]["wi"].sharding.spec)
    assert "fsdp" not in p_spec and "data" not in p_spec
    assert "fsdp" in m_spec or "data" in m_spec


def test_bf16_training():
    engine = _make_engine(zero_stage=2, dtype="bf16")
    batch = random_tokens(16)
    losses = [float(engine.train_batch(batch)["loss"]) for _ in range(4)]
    assert losses[-1] < losses[0]
    # master params stay fp32
    assert engine.state["params"]["wte"].dtype == jnp.float32


def test_fp16_dynamic_loss_scale_overflow_skip():
    engine = _make_engine(zero_stage=1, dtype="fp16")
    # poison one param so grads overflow under fp16 compute
    engine.state["params"]["wte"] = engine.state["params"]["wte"].at[0, 0].set(1e30)
    scale0 = engine.loss_scale
    m = engine.train_batch(random_tokens(16))
    assert bool(jax.device_get(m["overflow"]))
    assert engine.skipped_steps == 1
    # default hysteresis=2 (reference loss_scaler.py:154): the first overflow
    # burns the hysteresis counter, the second halves the scale
    assert engine.loss_scale == scale0
    engine.train_batch(random_tokens(16))
    assert engine.skipped_steps == 2
    assert engine.loss_scale == scale0 / 2
    assert engine.get_global_step() == 0  # updates skipped


def test_gradient_accumulation_equivalence():
    """gas=2 over the same data == gas=1 with double micro-batch. Uses SGD so
    the comparison is linear in the gradients (one Adam step at v≈0 would
    amplify fp32 accumulation-order noise past any tight tolerance)."""
    b = random_tokens(16)
    sgd = {"type": "SGD", "params": {"lr": 1e-2}}
    e1 = _make_engine(zero_stage=0, optimizer=sgd, train_batch_size=16, train_micro_batch_size_per_gpu=1, gradient_accumulation_steps=2)
    e2 = _make_engine(zero_stage=0, optimizer=sgd, train_batch_size=16, train_micro_batch_size_per_gpu=2, gradient_accumulation_steps=1)
    l1 = float(e1.train_batch(b)["loss"])
    l2 = float(e2.train_batch(b)["loss"])
    assert l1 == pytest.approx(l2, rel=1e-5)
    p1 = jax.device_get(e1.state["params"]["wte"])
    p2 = jax.device_get(e2.state["params"]["wte"])
    np.testing.assert_allclose(p1, p2, rtol=2e-4, atol=2e-6)


def test_compat_forward_backward_step():
    """The reference 3-call loop (engine.py:1596/:1743/:1950)."""
    engine = _make_engine(zero_stage=1)
    batch = random_tokens(16)
    micro = {"tokens": batch["tokens"][:8]}
    micro2 = {"tokens": batch["tokens"][8:]}
    l0 = float(engine.forward(micro))
    engine.backward()
    engine.step()  # mid-accumulation: no-op
    assert engine.get_global_step() == 0
    engine.forward(micro2)
    engine.backward()
    assert engine.is_gradient_accumulation_boundary()
    engine.step()
    assert engine.get_global_step() == 1
    l1 = float(engine.forward(micro))
    assert l1 < l0


def test_checkpoint_roundtrip(tmp_path):
    """save → load → bitwise state equality (reference: tests/unit/checkpoint
    compare_model_states)."""
    engine = _make_engine(zero_stage=2)
    batch = random_tokens(16)
    for _ in range(3):
        engine.train_batch(batch)
    engine.save_checkpoint(str(tmp_path), client_state={"note": "hi"})

    engine2 = _make_engine(zero_stage=2)
    tag, client = engine2.load_checkpoint(str(tmp_path))
    assert tag == "global_step3"
    assert client["note"] == "hi"
    assert engine2.global_steps == 3
    np.testing.assert_array_equal(
        jax.device_get(engine.state["params"]["wte"]), jax.device_get(engine2.state["params"]["wte"])
    )
    np.testing.assert_array_equal(
        jax.device_get(engine.state["opt"]["m"]["layers"]["wi"]),
        jax.device_get(engine2.state["opt"]["m"]["layers"]["wi"]),
    )
    # training continues identically
    m1 = engine.train_batch(batch)
    m2 = engine2.train_batch(batch)
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-6)


@pytest.mark.slow  # ~8s warm; cross-topology reshard parity stays warm in
# test_checkpoint.py::test_cross_topology_reshard
def test_checkpoint_reshard_across_zero_stages(tmp_path):
    """A ZeRO-3 checkpoint loads into a stage-1 engine (elastic re-partitioning,
    reference stage_1_and_2.py:2068 — free here via device_put resharding)."""
    e3 = _make_engine(zero_stage=3, mesh_over={"data": 2, "fsdp": 4})
    e3.train_batch(random_tokens(16))
    e3.save_checkpoint(str(tmp_path))
    e1 = _make_engine(zero_stage=1)
    e1.load_checkpoint(str(tmp_path))
    np.testing.assert_allclose(
        jax.device_get(e3.state["params"]["wte"]), jax.device_get(e1.state["params"]["wte"])
    )


def test_lr_schedule_in_step():
    engine = _make_engine(
        zero_stage=0,
        scheduler={"type": "WarmupLR", "params": {"warmup_min_lr": 0.0, "warmup_max_lr": 1e-3, "warmup_num_steps": 10, "warmup_type": "linear"}},
    )
    batch = random_tokens(16)
    m1 = engine.train_batch(batch)
    m5 = None
    for _ in range(4):
        m5 = engine.train_batch(batch)
    assert float(m5["lr"]) > float(m1["lr"])


def test_eval_batch():
    engine = _make_engine(zero_stage=1)
    loss = engine.eval_batch(random_tokens(16))
    assert np.isfinite(loss)


def test_zero_opt_state_bias_leaves_sharded():
    """Every leaf's optimizer state takes the ZeRO axis, including biases whose
    logical axes carry no ZeRO rule (reference shards *all* flat-buffer slices
    across DP ranks, stage_1_and_2.py:93 — round-2 weak #7)."""
    engine = _make_engine(zero_stage=2)
    for name in ("bq", "bk", "bv", "bi"):
        m_spec = str(engine.state["opt"]["m"]["layers"][name].sharding.spec)
        assert "fsdp" in m_spec or "data" in m_spec, f"{name} opt state replicated: {m_spec}"
    # params themselves stay replicated at stage 2
    p_spec = str(engine.state["params"]["layers"]["bq"].sharding.spec)
    assert "fsdp" not in p_spec and "data" not in p_spec


def test_zero3_bias_params_sharded():
    engine = _make_engine(zero_stage=3)
    spec = str(engine.state["params"]["layers"]["bq"].sharding.spec)
    assert "fsdp" in spec or "data" in spec


@pytest.mark.slow  # ~6s warm (synced per-step timers); the timer plumbing
# is also exercised warm by telemetry step-time histograms
def test_wall_clock_breakdown_times_steps():
    """wall_clock_breakdown=True activates the per-step synced timers
    (reference EngineTimers, engine.py:139-177) instead of being parsed and
    dropped."""
    engine = _make_engine(zero_stage=0, wall_clock_breakdown=True)
    batch = random_tokens(16)
    engine.train_batch(batch)
    engine.train_batch(batch)
    assert engine.timers("train_batch").count == 2
    assert engine.timers("train_batch").elapsed(reset=False) > 0
    assert engine.timers("step_dispatch").count == 2
    # off by default: no timers populated
    engine2 = _make_engine(zero_stage=0)
    engine2.train_batch(batch)
    assert "train_batch" not in engine2.timers.timers


def _pld_sparse_engine():
    model = tiny_transformer(max_seq_len=64)
    cfg = base_config()
    cfg["mesh"] = {"data": -1}
    cfg["progressive_layer_drop"] = {"enabled": True, "theta": 0.6, "gamma": 0.002}
    cfg["sparse_attention"] = {"mode": "fixed", "block": 16, "num_local_blocks": 2,
                               "num_global_blocks": 1}
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=cfg)
    return engine


def test_pld_and_sparse_attention_config_blocks_reach_model():
    """progressive_layer_drop / sparse_attention DS-config blocks translate
    into model-config fields instead of being parsed and dropped."""
    mc = _pld_sparse_engine().model.config
    assert mc.pld_enabled and mc.pld_theta == 0.6 and mc.pld_gamma == 0.002
    assert mc.attn_impl == "sparse" and mc.sparsity["mode"] == "fixed"


@pytest.mark.slow  # the interpret-mode sparse kernel executes ~seq^2-slow
# on CPU: this single train step is ~15-20s of the tier-1 budget (it was
# 128s at 64-seq/3-steps before PR 2 shrank it). The config-plumbing
# contract above stays warm, and test_sparse_attention keeps the sparse
# fwd/bwd/train path covered warm on its own (smaller) geometry.
def test_pld_and_sparse_attention_engine_trains():
    """The pld+sparse engine still trains on the sparse kernel path (finite
    loss through sparse fwd/bwd/update)."""
    engine = _pld_sparse_engine()
    batch = {"tokens": np.random.default_rng(0).integers(0, 128, (16, 33)).astype(np.int32)}
    assert np.isfinite(float(engine.train_batch(batch)["loss"]))


def test_save_16bit_model_and_consolidated_state_dict(tmp_path):
    """save_16bit_model / _zero3_consolidated_16bit_state_dict (reference
    engine.py:3264/:3194): full unsharded compute-dtype weights from a ZeRO-3
    sharded engine."""
    engine = _make_engine(zero_stage=3, dtype="bf16")
    engine.train_batch(random_tokens(16))
    sd = engine._zero3_consolidated_16bit_state_dict()
    key = [k for k in sd if k.endswith("layers/wq")][0]
    assert sd[key].dtype.name == "bfloat16"
    assert sd[key].shape == engine.state["params"]["layers"]["wq"].shape

    assert engine.save_16bit_model(str(tmp_path))
    import torch

    loaded = torch.load(str(tmp_path / "model_weights.pt"), weights_only=True)
    t = loaded[key]
    assert t.dtype == torch.bfloat16
    np.testing.assert_allclose(
        t.float().numpy(), np.asarray(sd[key]).astype(np.float32), rtol=1e-6)


def test_pjit_matches_single_device_loss():
    """Determinism sanitizer (SURVEY §5): the 8-device pjit loss equals the
    same computation on one device — the compiled SPMD program introduces no
    numerical divergence beyond reduction order."""
    model = tiny_transformer()
    params = model.init(jax.random.PRNGKey(0))
    batch = random_tokens(16)
    single = float(jax.jit(model.loss)(params, batch))

    engine = _make_engine(zero_stage=2)
    # replace engine params with the reference init for an exact comparison
    engine.state["params"] = jax.jit(
        lambda p: p, out_shardings=engine._state_shardings["params"])(params)
    dist_loss = float(engine.eval_batch(batch))
    np.testing.assert_allclose(dist_loss, single, rtol=2e-5)


def test_debug_sanitizers_nan_and_donation():
    """SURVEY §5 sanitizer row: the debug config group's jax_debug_nans
    toggle surfaces the first NaN-producing op, and donation_check verifies
    the compiled step consumed the donated state buffers."""
    import deepspeed_tpu
    from simple_model import base_config, random_tokens, tiny_transformer

    # donation_check: healthy engine -> all buffers consumed, no warning
    cfg = base_config()
    cfg["mesh"] = {"data": -1}
    cfg["debug"] = {"donation_check": True}
    engine, _, _, _ = deepspeed_tpu.initialize(model=tiny_transformer(), config=cfg)
    batch = random_tokens(16)
    engine.train_batch(batch)
    assert engine._donation_checked

    # nan_check: a poisoned batch raises at the first NaN-producing op
    # instead of silently propagating. jax_debug_nans is process-global —
    # restore it even on failure.
    cfg2 = base_config()
    cfg2["mesh"] = {"data": -1}
    cfg2["debug"] = {"nan_check": True}
    try:
        e2, _, _, _ = deepspeed_tpu.initialize(model=tiny_transformer(), config=cfg2)
        assert jax.config.jax_debug_nans
        e2.train_batch(batch)  # clean batch: runs fine (donation disabled)
    finally:
        jax.config.update("jax_debug_nans", False)


@pytest.mark.smoke
@pytest.mark.slow  # ~9s warm; zero-stage train matrix + checkpoint
# roundtrip/reshard tests keep both halves warm separately
def test_smoke_zero3_bf16_train_checkpoint_resume(tmp_path):
    """Smoke-tier composite (one engine build buys ZeRO-3 sharding + bf16
    masters + train + checkpoint save/load/resume coverage — the four
    separate full-suite tests each pay their own ~25 s mesh compile)."""
    engine = _make_engine(zero_stage=3, dtype="bf16", mesh_over={"data": 2, "fsdp": 4})
    batch = random_tokens(16)
    l0 = float(jax.device_get(engine.train_batch(batch)["loss"]))
    l1 = float(jax.device_get(engine.train_batch(batch)["loss"]))
    assert np.isfinite([l0, l1]).all() and l1 < l0
    # params actually sharded over fsdp (stage 3)
    wq = engine.state["params"]["layers"]["wq"]
    assert not wq.sharding.is_fully_replicated
    engine.save_checkpoint(str(tmp_path))
    step_saved = int(jax.device_get(engine.state["step"]))
    e2 = _make_engine(zero_stage=3, dtype="bf16", mesh_over={"data": 2, "fsdp": 4})
    e2.load_checkpoint(str(tmp_path))
    assert int(jax.device_get(e2.state["step"])) == step_saved
    l2 = float(jax.device_get(e2.train_batch(batch)["loss"]))
    assert np.isfinite(l2) and l2 < l0
