#!/usr/bin/env python3
"""What the chip says of a checkpoint that keeps more than ``save_flash`` (PR 50):
the train cell's widths at as many layers as the attached chips hold, the
cell's 8 x 2,048 tokens a chip, so a layer's saved bytes and its recomputed
GEMMs are the cell's own.

    chiprun -- python3 experiments/remat_chip.py [--layers 6] [--steps 8] [--set KEY=JSON ...]
    chiprun --chips 4 -- python3 experiments/remat_chip.py --fsdp 4 --layers 4 --grads

One child process a variant (the chip belongs to one process; the parent stays
off jax). Timed: ``floor`` (save_flash as written) and ``planned`` (the
engine's own choice from ``bytes_limit``): the step time, the device's
``memory_stats()`` and the losses; a step time here is a short model's, and the
DIFFERENCE a layer is what carries to the cell. ``--grads``: one step's
gradients of the floor program, of the program that keeps the candidate and of
the same step computed in float32, leaf by leaf on the device: where the two
bfloat16 programs differ, and how far each is from float32.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
VARIANTS = ("floor", "planned")


def _engine(args, compute_f32=False, clip=None):
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from chipbench.drivers.train import ds_config
    from chipbench.rehearse_compile import _load
    from deepspeed_tpu.comm.mesh import MeshConfig, build_mesh
    from deepspeed_tpu.models import transformer as tfm
    from deepspeed_tpu.utils.jax_env import use_compile_cache

    use_compile_cache()
    cell = _load("workloads", "pythia-1.4b.train-zero3-x4")
    program = _load("configs", cell["config"])["rehearse_program" if args.tiny else "program"]
    if args.tiny:  # a CPU rehearsal of this script: the cell's rehearsal sizes
        cell = {**cell, "job": {**cell["job"], **cell["rehearse"]["job"]},
                "tuning": cell["rehearse"]["tuning"]}
    micro = args.micro or cell["tuning"]["micro_batch_per_chip"]
    job = {**cell["job"], "mesh": {"data": 1, "fsdp": args.fsdp},
           "sequences_per_step": micro * args.fsdp}
    if clip is not None:
        job["gradient_clipping"] = clip
    cfg = tfm.TransformerConfig(dtype=jnp.float32 if compute_f32 else jnp.bfloat16, **{
        **program, **cell["tuning"]["model"], "num_layers": args.layers,
        "max_seq_len": job["sequence_length"],
        **{k: json.loads(v) for k, v in (kv.split("=", 1) for kv in args.set)}})
    config = ds_config(job, micro, args.fsdp)
    if compute_f32:
        config["bf16"] = {"enabled": False}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=tfm.Model(cfg), config=config, rng=jax.random.PRNGKey(args.seed),
        mesh=build_mesh(MeshConfig(**job["mesh"]), devices=jax.devices()[:args.fsdp]))
    return engine, cfg, (micro * args.fsdp, job["sequence_length"] + 1)


def timed(args) -> None:
    import jax
    import numpy as np

    engine, cfg, shape = _engine(args)
    if args.variant == "floor":
        engine._remat_floor_only = True  # the planner off
    rng = np.random.default_rng(args.seed)
    batches = [rng.integers(0, cfg.vocab_size, shape, dtype=np.int32) for _ in range(4)]
    losses, times = [], []
    for i in range(args.steps + 2):
        t0 = time.perf_counter()
        m = engine.train_batch({"tokens": batches[i % len(batches)]})
        losses.append(float(jax.block_until_ready(m["loss"])))
        times.append(time.perf_counter() - t0)
    stats = jax.devices()[0].memory_stats() or {}
    snap = engine.telemetry_snapshot()
    row = next((r for r in snap.get("program_ledger") or [] if r["name"] == "train/train_step"), {})
    print("RESULT " + json.dumps({
        "variant": args.variant, "layers": args.layers, "fsdp": args.fsdp, "set": args.set,
        "device": jax.devices()[0].device_kind,
        "step_ms_p50": 1e3 * statistics.median(times[2:]), "step_ms_min": 1e3 * min(times[2:]),
        "first_call_s": times[0], "losses": losses,
        "bytes_limit": stats.get("bytes_limit"), "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "remat_saved": row.get("remat_saved"), "remat_saved_bytes": row.get("remat_saved_bytes"),
        "gauge": snap["metrics"]["gauges"].get("train/remat_saved_bytes"),
    }), flush=True)


def grads(args) -> None:
    """One step's gradients three ways on the same parameters and batch. The
    engine's own programs: ``_build_train_step(grads_only=True)`` ends at the
    gradients (unclipped here), through the same ``fwd_bwd`` and shardings."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models import transformer as tfm

    engine, cfg, shape = _engine(args, clip=0.0)
    _, names, _ = tfm.remat_candidates(cfg)
    batch = {"tokens": np.random.default_rng(args.seed).integers(
        0, cfg.vocab_size, shape, dtype=np.int32)}
    engine._remat_floor_only = True  # the planner off: the names are forced here
    out = {}
    for what, kept in (("floor", ()), ("kept", names)):
        with tfm.remat_also_saving(kept):  # read as the first call traces the step
            g, m = engine._build_train_step(grads_only=True)(engine.state, batch)
        out[what] = (jax.tree.map(lambda x: x.astype(jnp.float32), g), float(m["loss"]))
    params = engine.state["params"]
    del engine
    ref_engine, _, _ = _engine(args, compute_f32=True, clip=0.0)
    ref_engine._remat_floor_only = True
    state = {**ref_engine.state, "params": params}
    g, m = ref_engine._build_train_step(grads_only=True)(state, batch)
    out["float32"] = (g, float(m["loss"]))

    @jax.jit
    def compare(floor, kept, ref):
        def leaf(path, f, k, r):  # a stacked leaf: one row a layer
            over = tuple(range("layers" in jax.tree_util.keystr(path), f.ndim))
            norm = lambda x: jnp.sqrt(jnp.sum(jnp.square(x), axis=over))
            return jnp.stack([norm(f - k) / norm(r), norm(f - r) / norm(r), norm(k - r) / norm(r),
                              jnp.mean((f != k).astype(jnp.float32), axis=over)], axis=-1)
        return jax.tree_util.tree_map_with_path(leaf, floor, kept, ref)

    table = compare(out["floor"][0], out["kept"][0], out["float32"][0])
    rows = {jax.tree_util.keystr(path): np.asarray(x).round(6).tolist()
            for path, x in jax.tree_util.tree_flatten_with_path(table)[0]}
    print("RESULT " + json.dumps({
        "layers": args.layers, "fsdp": args.fsdp, "device": jax.devices()[0].device_kind,
        "kept": list(names), "loss": {k: v[1] for k, v in out.items()},
        "columns": ["|floor - kept| / |float32|", "|floor - float32| / |float32|",
                    "|kept - float32| / |float32|", "share of elements that differ"],
        "leaves": rows}), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--layers", type=int, default=6)
    ap.add_argument("--fsdp", type=int, default=1)
    ap.add_argument("--micro", type=int, default=0, help="sequences a chip; 0: the cell's")
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--seed", type=int, default=50)
    ap.add_argument("--set", nargs="*", default=[], metavar="KEY=JSON",
                    help="model sizes other than the cell's")
    ap.add_argument("--grads", action="store_true")
    ap.add_argument("--variant", choices=VARIANTS + ("grads",))
    ap.add_argument("--tiny", action="store_true", help="rehearsal sizes (for the CPU)")
    args = ap.parse_args()
    if args.variant:
        return grads(args) if args.variant == "grads" else timed(args)
    results = {}
    for v in (("grads",) if args.grads else VARIANTS):
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--variant", v, "--layers", str(args.layers),
             "--fsdp", str(args.fsdp), "--micro", str(args.micro), "--steps", str(args.steps),
             "--seed", str(args.seed), "--set", *args.set] + ["--tiny"] * args.tiny,
            capture_output=True, text=True)
        lines = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")]
        for l in (out.stdout + out.stderr).splitlines():
            if "remat:" in l:
                print(v, "|", l[-400:], flush=True)
        if not lines:
            print(json.dumps({"variant": v, "rc": out.returncode, "stderr": out.stderr[-1500:]}),
                  flush=True)
            continue
        results[v] = json.loads(lines[-1][len("RESULT "):])
        print(json.dumps(results[v]), flush=True)
    base = results.get("floor")
    if base:
        for v, r in results.items():
            print(json.dumps({
                "variant": v, "losses_are_the_floors": r["losses"] == base["losses"],
                "ms_a_layer_saved": (base["step_ms_p50"] - r["step_ms_p50"]) / args.layers}),
                flush=True)


if __name__ == "__main__":
    main()
