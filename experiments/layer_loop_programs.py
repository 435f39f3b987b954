"""What the layer loop compiles to, per configuration of the benchmark (PR 47).

For every ``chipbench/configs/*.json`` at each of its rehearsal programs: lower
``apply``, the gradient of ``causal_lm_loss`` and ``apply_with_cache`` (a block
that fills its cache, and a one-token step) and print XLA's own count of the
compiled program: flops, bytes accessed and the ``while`` operations of the
optimised HLO. Two trees are compared by running this file in each
(``PYTHONPATH=<tree> python experiments/layer_loop_programs.py > <tree>.json``)
and ``--diff a.json b.json``. Nothing runs; on the CPU the counts are the CPU
compiler's, which is enough to see a scan that became blocks or a copy that
appeared. Times come from the chip.
"""
import glob
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def _count(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    text = compiled.as_text()
    return {"flops": cost.get("flops"), "bytes": cost.get("bytes accessed"),
            "while": sum(" while(" in line for line in text.splitlines())}


def programs(root="chipbench/configs"):
    from deepspeed_tpu.models import transformer as tfm

    out = {}
    for path in sorted(glob.glob(os.path.join(root, "*.json"))):
        with open(path) as f:
            config = json.load(f)
        for key in sorted(k for k in config if k.startswith("rehearse") and k.endswith("program")):
            cfg = tfm.TransformerConfig(dtype=jnp.bfloat16, **config[key])
            tfm._ACTIVE_MESH[0] = None
            held = jax.eval_shape(lambda: tfm.init(cfg, jax.random.PRNGKey(0)))
            served = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                a.shape, cfg.dtype if a.dtype == jnp.float32 else a.dtype), held)
            B, T, Smax = 2, 64, 128
            tokens = jax.ShapeDtypeStruct((B, T), jnp.int32)
            name = f"{config['name']}.{key}"
            out[f"{name}.apply"] = _count(lambda p, t: tfm.apply(cfg, p, t), held, tokens)
            for remat in (False, True):
                try:
                    train = cfg.replace(remat=remat, loss_chunk_size=32)
                except NotImplementedError as refused:
                    out[f"{name}.grad.remat"] = str(refused)
                    continue
                out[f"{name}.grad{'.remat' if remat else ''}"] = _count(
                    jax.grad(lambda p, t: tfm.causal_lm_loss(train, p, {"tokens": t})),
                    held, jax.ShapeDtypeStruct((B, T + 1), jnp.int32))
            fill = jax.eval_shape(lambda: tfm.init_cache(cfg, B, T))
            out[f"{name}.prefill"] = _count(
                lambda p, t, c: tfm.apply_with_cache(cfg, p, t, c, 0, last_only=True),
                served, tokens, fill)
            slots = jax.eval_shape(lambda: tfm.init_cache(cfg, B, Smax))
            out[f"{name}.step"] = _count(
                lambda p, t, c, pos: tfm.apply_with_cache(cfg, p, t, c, pos),
                served, jax.ShapeDtypeStruct((B, 1), jnp.int32), slots,
                jax.ShapeDtypeStruct((B,), jnp.int32))
    return out


def diff(a, b):
    with open(a) as f:
        a = json.load(f)
    with open(b) as f:
        b = json.load(f)
    for name in sorted(set(a) | set(b)):
        left, right = a.get(name), b.get(name)
        print(f"{'same' if left == right else 'DIFF'}  {name}  {left}  {right if left != right else ''}")


if __name__ == "__main__":
    if sys.argv[1:2] == ["--diff"]:
        diff(*sys.argv[2:4])
    else:
        print(json.dumps(programs(), indent=1))
