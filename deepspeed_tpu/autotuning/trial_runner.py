"""One autotuning trial in an isolated process (see scheduler.py).

Reads a JSON spec, builds the transformer + engine, measures steady-state
step time, prints ONE JSON result line on stdout. Crashes/OOMs/hangs are the
PARENT's problem to classify — this process just dies with them. The
reference's per-experiment training job (autotuning/scheduler.py:27 launches
``deepspeed ...`` per exp) collapses to this runner because one process owns
the whole device mesh.
"""

from __future__ import annotations

import json
import sys
import time


def main(spec_path: str) -> int:
    with open(spec_path) as f:
        spec = json.load(f)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models.transformer import Model, TransformerConfig
    from deepspeed_tpu.utils.jax_env import use_compile_cache

    use_compile_cache()

    mc = dict(spec["model_cfg"])
    if isinstance(mc.get("dtype"), str):
        mc["dtype"] = jnp.bfloat16 if mc["dtype"] == "bfloat16" else jnp.float32
    model = Model(TransformerConfig(**mc))
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=spec["ds_config"])

    b = spec["batch"]
    tokens = np.random.default_rng(0).integers(
        0, b["vocab"], size=(b["size"], b["seq"] + 1)).astype(np.int32)
    batch = {"tokens": tokens}

    def sync(m):
        jax.block_until_ready(m["loss"])

    t_c0 = time.perf_counter()
    sync(engine.train_batch(batch))  # compile + first step
    compile_s = time.perf_counter() - t_c0
    m = None
    for _ in range(int(spec.get("warmup", 2))):
        m = engine.train_batch(batch)
    if m is not None:
        sync(m)
    steps = int(spec.get("steps", 5))
    t0 = time.perf_counter()
    for _ in range(steps):
        m = engine.train_batch(batch)
    sync(m)
    dt = (time.perf_counter() - t0) / steps

    print(json.dumps({
        "status": "ok",
        "step_ms": round(dt * 1e3, 3),
        "tokens_per_sec": round(b["size"] * b["seq"] / dt, 1),
        "compile_s": round(compile_s, 2),
        "platform": jax.devices()[0].platform,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
