#!/usr/bin/env python3
"""Does the train cell's step fit a v5e with more than ``save_flash`` saved?
Compile-only, for a 2x2 v5e that is described and not attached (PR 50).

``chipbench/rehearse_compile.py train`` compiles the cell's ZeRO-3 step at its
real sizes, but on the CPU platform (no ``memory_stats()``) over a one-layer
twin's state, so the engine's plan of what a checkpoint keeps
(``runtime/remat_plan.py``) adds nothing there. This is that rehearsal with the
chip's memory limit and the FULL state's bytes a device handed to the planner:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 NPROC=32 \
        python3 experiments/remat_fit.py [--layers 24] [--fsdp 4] [--dump DIR]

It prints ``memory_analysis()`` a device of the floor program, of the program
with the candidate forced, and of the program the planner itself chooses, or
the compiler's refusal; ``--micro``, ``--seq``, ``--layers``, ``--fsdp`` and
``--set`` give the step other shapes than the cell's. The planner's count of a
step's temporaries (``models/transformer.step_working_bytes``) and its
``HEADROOM`` are set from these lines: the floor program's peak - the state -
the floor's residuals at each shape (PERF.md section 6, PR 50).
Nothing runs, so nothing printed here is a time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding

V5E_BYTES_LIMIT = int(15.75 * 2 ** 30)  # memory_stats()["bytes_limit"] of one v5e chip
GB = 1e9


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--layers", type=int, default=0, help="0: the configuration's")
    ap.add_argument("--fsdp", type=int, default=4)
    ap.add_argument("--limit", type=int, default=V5E_BYTES_LIMIT)
    ap.add_argument("--micro", type=int, default=0, help="sequences a chip; 0: the cell's")
    ap.add_argument("--seq", type=int, default=0, help="sequence length; 0: the cell's")
    ap.add_argument("--set", nargs="*", default=[], metavar="KEY=JSON",
                    help="model sizes other than the cell's, e.g. ffn_size=5632 activation='\"swiglu\"'")
    ap.add_argument("--programs", nargs="*", default=["floor", "forced", "planned"])
    ap.add_argument("--dump", default="", help="a directory for each program's compiled HLO text")
    args = ap.parse_args()

    from jax.experimental import topologies

    from chipbench.drivers.train import ds_config
    from chipbench.rehearse_compile import _full_state, _load
    from deepspeed_tpu.comm.mesh import MeshConfig, build_mesh
    from deepspeed_tpu.models import transformer as tfm
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    from deepspeed_tpu.utils.memory import device_bytes_held

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    jax.default_backend = lambda: "tpu"  # the program's TPU branches (compiled kernels)

    cell = _load("workloads", "pythia-1.4b.train-zero3-x4")
    program = _load("configs", cell["config"])["program"]
    micro = args.micro or cell["tuning"]["micro_batch_per_chip"]
    job = {**cell["job"], "mesh": {"data": 1, "fsdp": args.fsdp},
           "sequences_per_step": micro * args.fsdp}
    if args.seq:
        job["sequence_length"] = args.seq
    sizes = {**program, **cell["tuning"]["model"], "max_seq_len": job["sequence_length"],
             **{k: json.loads(v) for k, v in (kv.split("=", 1) for kv in args.set)}}
    if args.layers:
        sizes["num_layers"] = args.layers
    small = tfm.TransformerConfig(dtype=jnp.bfloat16, **{**sizes, "num_layers": 1,
                                                         "vocab_size": 512})
    engine = DeepSpeedEngine(
        model=tfm.Model(small), config=ds_config(job, micro, args.fsdp),
        mesh=build_mesh(MeshConfig(**job["mesh"]), devices=jax.devices()[:args.fsdp]))
    mesh = Mesh(np.asarray(topo.devices[:args.fsdp]).reshape(engine.mesh.devices.shape),
                engine.mesh.axis_names)
    full = tfm.Model(tfm.TransformerConfig(dtype=jnp.bfloat16, **sizes))
    engine.mesh, engine.model = mesh, full
    full.set_mesh(mesh)
    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s.spec, memory_kind=s.memory_kind),
        engine._state_shardings, is_leaf=lambda x: isinstance(x, NamedSharding))
    engine._state_shardings = shardings
    state = _full_state(engine.state, jax.eval_shape(full.init, jax.random.PRNGKey(0)), shardings)
    state_bytes = device_bytes_held(state)
    batch = {"tokens": jax.ShapeDtypeStruct(
        (job["sequences_per_step"], job["sequence_length"] + 1), jnp.int32,
        sharding=NamedSharding(mesh, engine.batch_spec))}
    _, every, _ = tfm.remat_candidates(full.config)
    print(json.dumps({"limit_gb": args.limit / GB, "state_gb_a_device": state_bytes / GB,
                      "layers": full.config.num_layers, "fsdp": args.fsdp, "micro": micro,
                      "seq": job["sequence_length"], "set": args.set}), flush=True)

    def report(what, build):
        t0 = time.perf_counter()
        try:
            compiled = build().lower(state, batch).compile()
        except Exception as e:  # noqa: BLE001 -- the compiler's refusal is the result
            print(json.dumps({"program": what, "refused": str(e)[:400]}), flush=True)
            return
        ma = compiled.memory_analysis()
        text = compiled.as_text()
        if args.dump:
            os.makedirs(args.dump, exist_ok=True)
            with open(os.path.join(args.dump, what.split(":")[0].replace(" ", "_") + ".hlo"), "w") as f:
                f.write(text)
        print(json.dumps({
            "program": what, "compile_s": round(time.perf_counter() - t0, 1),
            "argument_gb": ma.argument_size_in_bytes / GB, "temp_gb": ma.temp_size_in_bytes / GB,
            "peak_gb": ma.peak_memory_in_bytes / GB,
            "footprint_gb": (ma.argument_size_in_bytes + ma.output_size_in_bytes
                             + ma.temp_size_in_bytes - ma.alias_size_in_bytes) / GB,
            "convolutions": text.count(" convolution("), "fusions": text.count(" fusion("),
        }), flush=True)

    def forced(names):
        def build():
            step = engine._build_train_step()

            class Lowered:  # the names are read as the step is traced: in ``lower``
                @staticmethod
                def lower(*a):
                    with tfm.remat_also_saving(names):
                        return step.lower(*a)
            return Lowered
        return build

    if "floor" in args.programs:
        report("floor (save_flash)", engine._build_train_step)
    if "forced" in args.programs:
        report(f"forced: {'+'.join(every)}", forced(every))
    engine._remat_plans.clear()
    if "planned" in args.programs:
        report("planned", lambda: engine._build_train_step(
            remat_limit=args.limit, remat_state=state))
    print(json.dumps({"plan": [(list(p.names), p.saved_bytes / GB, p.room / GB)
                               for p in engine._remat_plans.values()]}), flush=True)


if __name__ == "__main__":
    main()
