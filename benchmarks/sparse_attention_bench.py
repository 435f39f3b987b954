"""Block-sparse attention long-sequence benchmark.

The reference's block-sparse kernels claim ~6x attention speedups and 10x
longer sequences (docs/_posts/2020-09-08-sparse-attention-news.md:9). This
harness times dense flash vs block-sparse flash fwd+bwd at long sequence
lengths and prints one JSON line with the speedup.

Usage: python benchmarks/sparse_attention_bench.py [--seq 8192] [--mode bigbird]
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np


def _sync(x):
    np.asarray(jax.device_get(jax.tree.leaves(x)[0].ravel()[0]))


def timeit(fn, *args, n=5, warmup=2):
    for _ in range(warmup):
        out = fn(*args)
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    _sync(out)
    return (time.perf_counter() - t0) / n


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--mode", default="bigbird",
                    choices=["fixed", "bigbird", "bslongformer"])
    # sparsity-pattern granularity. Grid-step cost lessons from the flash
    # block sweep (docs/PERF.md finding #1) apply here too: 128-blocks at 8k
    # sequence make ~2 MFLOP grid steps and the kernel loses to dense flash's
    # 512x1024 tiles despite 8x less math — 512-blocks amortize the grid.
    ap.add_argument("--block", type=int, default=None)
    args = ap.parse_args()

    platform = jax.default_backend()
    on_tpu = platform == "tpu"
    print(f"[sparse_attention_bench] platform={platform}" + (
        "" if on_tpu else " — not a TPU: seq 512, batch 1, block 128, float32 "
        "in the Pallas interpreter unless overridden; not a device number"),
        flush=True)
    S = args.seq or (8192 if on_tpu else 512)
    B = args.batch or (4 if on_tpu else 1)
    H, D = args.heads, args.dim

    import os, sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    from deepspeed_tpu.ops.sparse_attention import SPARSITY_CONFIGS, sparse_flash_attention

    kwargs = {"num_heads": H, "block": args.block or (512 if on_tpu else 128)}
    if args.mode == "bigbird":
        kwargs.update(num_random_blocks=2, num_sliding_window_blocks=3, num_global_blocks=1)
    elif args.mode == "bslongformer":
        kwargs.update(num_sliding_window_blocks=3, global_block_indices=[0])
    else:
        kwargs.update(num_local_blocks=4, num_global_blocks=1)
    scfg = SPARSITY_CONFIGS[args.mode](**kwargs)
    layout = scfg.make_layout(S)
    density = float(np.tril(np.asarray(layout[0], bool)).sum()) / (
        layout.shape[1] * (layout.shape[1] + 1) / 2
    )

    r = jax.random.PRNGKey(0)
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    q, k, v = (jax.random.normal(kk, (B, S, H, D), dt) for kk in jax.random.split(r, 3))

    dense_fb = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v, causal=True).astype(jnp.float32)),
        argnums=(0, 1, 2)))
    sparse_fb = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(sparse_flash_attention(q, k, v, layout).astype(jnp.float32)),
        argnums=(0, 1, 2)))

    t_dense = timeit(dense_fb, q, k, v)
    t_sparse = timeit(sparse_fb, q, k, v)
    out = {
        "metric": f"block-sparse attention fwd+bwd speedup vs dense flash ({args.mode}, seq {S})",
        "value": round(t_dense / t_sparse, 2),
        "unit": "x",
        "dense_ms": round(t_dense * 1e3, 2),
        "sparse_ms": round(t_sparse * 1e3, 2),
        "causal_block_density": round(density, 3),
        "platform": platform,
    }
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
