"""Aux-subsystem tests: flops profiler, monitor backends, env report,
comms logger (SURVEY §5 observability rows — mirrors the reference's
monitor/test_monitor.py + flops_profiler tests)."""

import csv
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models import transformer as tfm
from deepspeed_tpu.models.transformer import Model, TransformerConfig
from deepspeed_tpu.profiling.flops_profiler.profiler import (
    FlopsProfiler,
    count_jaxpr_flops,
    get_model_profile,
)


def test_jaxpr_flop_count_matmul_exact():
    def f(a, b):
        return a @ b

    jaxpr = jax.make_jaxpr(f)(jnp.zeros((64, 32)), jnp.zeros((32, 16)))
    total, by_op, _ = count_jaxpr_flops(jaxpr.jaxpr)
    assert total == 2 * 64 * 32 * 16
    assert by_op.get("dot_general") == total


def test_per_module_scope_tree_sums_to_aggregate():
    """VERDICT r4 #9: jaxpr FLOPs attributed to named scopes (embed /
    per-layer attn / ffn / lm_head) must sum to the aggregate, and the
    reference-style depth-limited tree report prints them
    (reference profiler.py:235 print_model_profile)."""
    from deepspeed_tpu.profiling.flops_profiler.profiler import scope_tree

    cfg = TransformerConfig(
        vocab_size=128, max_seq_len=32, num_layers=2, num_heads=2, hidden_size=32,
        dtype=jnp.float32, loss_chunk_size=0,
    )
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 16), jnp.int32)
    prof = FlopsProfiler()
    res = prof.profile(lambda p, t: model.apply(p, t), params, tokens,
                       time_it=False, params=params)
    # every counted FLOP lands in exactly one scope bucket
    assert sum(res.by_scope.values()) == res.total_flops
    tree = scope_tree(res.by_scope)
    assert tree["flops"] == res.total_flops
    kids = tree["children"]
    for name in ("embed", "attn", "ffn", "lm_head"):
        assert name in kids and kids[name]["flops"] > 0, (name, list(kids))
    # attn+ffn ride the length-2 layer scan: per-layer rows reflect L layers
    d, f = 32, 128
    T = 2 * 16
    assert kids["ffn"]["flops"] >= 2 * (2 * T * 2 * d * f)  # L * (2 matmuls)
    text = prof.print_model_profile(res, depth=2, top_modules=6)
    assert "per-module breakdown" in text and "ffn" in text and "attn" in text


def test_model_profile_matches_analytic():
    cfg = TransformerConfig(
        vocab_size=128, max_seq_len=32, num_layers=2, num_heads=2, hidden_size=32,
        dtype=jnp.float32, loss_chunk_size=0,
    )
    model = Model(cfg)
    flops, params, _ = get_model_profile(model, tokens_shape=(2, 16), time_it=False)
    # matmul flops must at least cover qkvo + mlp + logits for 2x16 tokens
    d, f, V, L, T = 32, 128, 128, 2, 2 * 16
    min_matmul = 2 * T * (L * (4 * d * d + 2 * d * f) + d * V)
    assert flops >= min_matmul
    assert params > 0


def test_profiler_times_compiled_fn():
    prof = FlopsProfiler()
    res = prof.profile(lambda x: (x @ x).sum(), jnp.eye(64), time_it=True)
    assert res.total_flops >= 2 * 64 * 64 * 64
    assert res.latency_s and res.latency_s > 0
    assert res.tflops_per_sec and res.tflops_per_sec > 0


def test_csv_monitor_writes_events(tmp_path):
    from deepspeed_tpu.monitor.monitor import MonitorMaster
    from deepspeed_tpu.runtime.config import DeepSpeedConfig

    cfg = DeepSpeedConfig.from_dict(
        {
            "train_batch_size": 8,
            "csv_monitor": {"enabled": True, "output_path": str(tmp_path), "job_name": "j"},
        },
        world_size=8,
    )
    mon = MonitorMaster(cfg)
    assert mon.enabled
    mon.write_events([("Train/loss", 1.5, 10), ("Train/loss", 1.2, 20)])
    files = [str(p) for p in tmp_path.rglob("*.csv")] if hasattr(tmp_path, "rglob") else []
    found = []
    for root, _, names in os.walk(tmp_path):
        for n in names:
            if n.endswith(".csv"):
                found.append(os.path.join(root, n))
    assert found, "csv monitor wrote no files"
    rows = list(csv.reader(open(found[0])))
    assert any("1.5" in ",".join(r) for r in rows)


def test_comms_logger_records_trace_time():
    from deepspeed_tpu.comm.logger import comms_logger
    from deepspeed_tpu import comm

    comms_logger.configure(enabled=True, verbose=False)
    try:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from deepspeed_tpu.comm.mesh import MeshConfig, build_mesh

        mesh = build_mesh(MeshConfig(data=-1))
        f = shard_map(
            lambda x: comm.all_reduce(x, "data"), mesh=mesh,
            in_specs=P("data"), out_specs=P(), check_vma=False,
        )
        jax.jit(f)(jnp.ones((8, 4)))
        summ = comms_logger.summary()
        keys = list(summ)
        assert any("all_reduce" in k for k in keys), keys
        rec = summ[[k for k in keys if "all_reduce" in k][0]]
        assert rec["count"] >= 1 and rec["bytes"] > 0
        # deprecated mutable-store access still works but warns
        import pytest as _pytest

        with _pytest.warns(DeprecationWarning):
            assert comms_logger.prof_ops
        # volumes also routed into the global telemetry registry
        from deepspeed_tpu.telemetry import get_registry

        snap = get_registry().snapshot()
        assert any(k.startswith("comm/all_reduce") and k.endswith("/bytes")
                   and v > 0 for k, v in snap["counters"].items()), snap["counters"]
        comms_logger.log_all()  # must not raise
    finally:
        comms_logger.configure(enabled=False, verbose=False)
        comms_logger.reset()
    # reset keeps both views consistent: internal store AND mirrored counters
    assert comms_logger.summary() == {}
    snap2 = get_registry().snapshot()
    assert all(v == 0 for k, v in snap2["counters"].items()
               if k.startswith("comm/")), snap2["counters"]


def test_env_report_runs():
    from deepspeed_tpu.env_report import collect

    info = collect()
    assert info["jax"]
    assert "native_aio" in info


def test_flops_profiler_config_block_runs_at_profile_step(capsys):
    """flops_profiler DS-config block triggers the profile print at
    profile_step (reference engine.py:1608-1627) instead of being ignored."""
    import deepspeed_tpu
    from simple_model import base_config, random_tokens, tiny_transformer

    model = tiny_transformer()
    cfg = base_config()
    cfg["mesh"] = {"data": -1}
    cfg["flops_profiler"] = {"enabled": True, "profile_step": 2, "detailed": False}
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=cfg)
    batch = random_tokens(16)
    engine.train_batch(batch)
    capsys.readouterr()
    engine.train_batch(batch)  # step 2: profile printed
    out = capsys.readouterr().out
    assert "flops profiler" in out and "params:" in out
