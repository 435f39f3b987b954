#!/usr/bin/env python3
"""Compile-only rehearsal: the cells' main programs at their REAL sizes for a
TPU v5e that is described, not attached. Nothing runs, so nothing printed
here is a time or a chip result; it prints what the chip's compiler would
refuse and ``memory_analysis()`` per device. Used to choose the train cell's
micro-batch and remat policy and the serving cells' ``n_slots`` before
spending chip time. Not part of a benchmark run.

    JAX_PLATFORMS=cpu python3 -m chipbench.rehearse_compile train [--micro 2 4 8] [--policy save_flash dots_and_flash]
    JAX_PLATFORMS=cpu python3 -m chipbench.rehearse_compile serve [--slots 16 24]
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

HERE = os.path.dirname(os.path.abspath(__file__))
GB = 1e9


def _load(directory: str, name: str) -> dict:
    with open(os.path.join(HERE, directory, f"{name}.json")) as f:
        return json.load(f)


def _report(what: str, compiled, t0: float) -> None:
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    text = compiled.as_text()
    print(json.dumps({
        "program": what, "compile_s": round(time.perf_counter() - t0, 1),
        "argument_gb": ma.argument_size_in_bytes / GB, "output_gb": ma.output_size_in_bytes / GB,
        "temp_gb": ma.temp_size_in_bytes / GB, "alias_gb": ma.alias_size_in_bytes / GB,
        "footprint_gb": total / GB, "pallas_calls": text.count("tpu_custom_call"),
        "all_gather": text.count("all-gather"), "reduce_scatter": text.count("reduce-scatter"),
        "all_reduce": text.count("all-reduce")}), flush=True)


def train(topo, micros, policies, chunks) -> None:
    """The ZeRO-3 step of the train cell for the described 2x2: the engine's
    state is never materialised (1.4 B parameters x 16 B): only its shapes and
    shardings are handed to the step builder."""
    from deepspeed_tpu.comm.mesh import MeshConfig, build_mesh
    from deepspeed_tpu.models.transformer import Model, TransformerConfig
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine

    from .drivers.train import ds_config

    cell = _load("workloads", "pythia-1.4b.train-zero3-x4")
    program = _load("configs", cell["config"])["program"]
    job = cell["job"]
    jax.default_backend = lambda: "tpu"  # the program's TPU branches (compiled kernels)
    for policy, micro, chunk in itertools.product(policies, micros, chunks):
        what = f"train zero3 fsdp=4 micro={micro} {policy} chunk={chunk}"
        sizes = {**program, **cell["tuning"]["model"], "remat_policy": policy,
                 "loss_chunk_size": chunk, "max_seq_len": job["sequence_length"]}
        # a ONE-layer twin builds the engine cheaply on the CPU; its specs are
        # per leaf, not per layer, so they serve the 24-layer shapes
        small = TransformerConfig(dtype=jnp.bfloat16, **{**sizes, "num_layers": 1,
                                                         "vocab_size": 512})
        engine = DeepSpeedEngine(
            model=Model(small), config=ds_config(job, micro, 4),
            mesh=build_mesh(MeshConfig(**job["mesh"]), devices=jax.devices()[:4]))
        mesh = Mesh(np.asarray(topo.devices).reshape(engine.mesh.devices.shape),
                    engine.mesh.axis_names)
        full = Model(TransformerConfig(dtype=jnp.bfloat16, **sizes))
        engine.mesh, engine.model = mesh, full
        full.set_mesh(mesh)
        shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s.spec, memory_kind=s.memory_kind),
            engine._state_shardings, is_leaf=lambda x: isinstance(x, NamedSharding))
        engine._state_shardings = shardings
        state = _full_state(engine.state, jax.eval_shape(full.init, jax.random.PRNGKey(0)),
                            shardings)
        batch = {"tokens": jax.ShapeDtypeStruct(
            (job["sequences_per_step"], job["sequence_length"] + 1), jnp.int32,
            sharding=NamedSharding(mesh, engine.batch_spec))}
        t0 = time.perf_counter()
        try:
            compiled = engine._build_train_step().lower(state, batch).compile()
        except Exception as e:  # noqa: BLE001 -- the compiler's refusal is the result
            print(json.dumps({"program": what, "refused": str(e)[:600]}), flush=True)
            continue
        _report(what, compiled, t0)


def _full_state(small_state, real_params, shardings):
    """Shapes of the full-size engine state: every leaf of the one-layer
    engine whose tree position is a parameter (params, and each optimizer
    moment) takes the full parameter's shape; scalars stay."""
    real_leaves = {jax.tree_util.keystr(p): v
                   for p, v in jax.tree_util.tree_flatten_with_path(real_params)[0]}

    def full(path, leaf, sharding):
        key = jax.tree_util.keystr(path)
        for suffix, v in real_leaves.items():
            if key.endswith(suffix) and leaf.ndim == v.ndim:
                return jax.ShapeDtypeStruct(v.shape, leaf.dtype, sharding=sharding)
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=sharding)

    return jax.tree_util.tree_map_with_path(full, small_state, shardings)


def serve(topo, slot_counts) -> None:
    """Decode and the 2048-bucket prefill of both configurations for one
    described chip, weights float32 as ``InferenceEngine`` holds them today."""
    from deepspeed_tpu.inference.serving import SlotWorker
    from deepspeed_tpu.models.transformer import Model, TransformerConfig

    jax.default_backend = lambda: "tpu"
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    for name in ("pythia-1.4b", "bloom-1b7"):
        program = _load("configs", name)["program"]
        cfg = TransformerConfig(dtype=jnp.bfloat16, **program)
        params = jax.tree.map(lambda x: sds(x.shape, x.dtype),
                              jax.eval_shape(Model(cfg).init, jax.random.PRNGKey(0)))
        weights = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(params))
        for n in slot_counts:
            smax = 2048
            kv = sds((cfg.num_layers, n, smax, cfg.num_heads, cfg.head_dim), jnp.bfloat16)
            cache = {"k": kv, "v": kv}
            worker = SlotWorker.__new__(SlotWorker)  # the builders read only these
            worker.cfg, worker.Smax = cfg, smax
            worker._cache_shardings = {"k": one, "v": one}
            key = sds((2,), jnp.uint32)
            vec = lambda d: sds((n,), d)
            one_of = lambda d: sds((1,), d)
            print(json.dumps({"config": name, "n_slots": n, "weights_gb": weights / GB,
                              "cache_gb": 2 * int(np.prod(kv.shape)) * 2 / GB}), flush=True)
            for what, lower in (
                ("decode", lambda: worker._build_decode().lower(
                    params, cache, vec(jnp.int32), vec(jnp.int32), vec(jnp.int32),
                    vec(jnp.bool_), key, vec(jnp.float32), vec(jnp.int32), vec(jnp.float32))),
                ("prefill[2048]", lambda: worker._build_prefill(2048).lower(
                    params, cache, sds((1, 2048), jnp.int32), sds((), jnp.int32),
                    sds((), jnp.int32), key, one_of(jnp.float32), one_of(jnp.int32),
                    one_of(jnp.float32))),
            ):
                t0 = time.perf_counter()
                try:
                    compiled = lower().compile()
                except Exception as e:  # noqa: BLE001 -- the compiler's refusal is the result
                    print(json.dumps({"program": f"{name} {what} n_slots={n}",
                                      "refused": str(e)[:600]}), flush=True)
                    continue
                _report(f"{name} {what} n_slots={n}", compiled, t0)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("what", choices=("train", "serve"))
    ap.add_argument("--micro", type=int, nargs="+", default=[2, 4])
    ap.add_argument("--policy", nargs="+", default=["save_flash"])
    ap.add_argument("--chunk", type=int, nargs="+", default=[512])
    ap.add_argument("--slots", type=int, nargs="+", default=[16, 24])
    args = ap.parse_args()
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)  # cannot be read back without a chip
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    if args.what == "train":
        train(topo, args.micro, args.policy, args.chunk)
    else:
        serve(topo, args.slots)


if __name__ == "__main__":
    main()
