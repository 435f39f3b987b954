"""The flash forward kernel alone at [64 heads, rows, 128] bf16 under a window of 128, on one chip:
the whole causal grid with the window as a runtime operand (what a window layer's prefill ran until
PR 40) against the banded forward at several (block_q, block_k, heads a step, heads
written out inline an iteration of the step's loop). Kernel calls are
chained inside ONE program (q <- out), so no host time is in a figure. PERF.md section 6 (PR 40) has
the table this printed.

    chiprun -- python experiments/flash_band_blocks.py
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas import flash_attention as fa

H, D, W = 64, 128, 128
INNER = 40
ROWS = (2048, 4096, 8192, 16384)
BANDS = [(128, 128, 8, 2), (256, 128, 1, 1), (256, 128, 4, 2), (256, 128, 8, 1), (256, 128, 8, 2),
         (256, 128, 8, 4), (256, 128, 8, 8), (512, 128, 8, 2), (256, 256, 8, 2)]


def timeit(fn, q, *a, n=3):
    """-> (ms a kernel call, one call's output). ``fn``: the single call, [BH, S, D] -> the same."""
    many = jax.jit(lambda q, *a: jax.lax.fori_loop(0, INNER, lambda i, x: fn(x, *a), q))
    out = jax.jit(fn)(q, *a)
    many(q, *a).block_until_ready()
    best = float("inf")
    for _ in range(n):
        t = time.perf_counter()
        many(q, *a).block_until_ready()
        best = min(best, (time.perf_counter() - t) / INNER)
    return best * 1e3, out


def main():
    print(jax.devices(), flush=True)
    table = {}
    for rows in ROWS:
        ks = jax.random.split(jax.random.PRNGKey(rows), 3)
        q, k, v = (jax.random.normal(kk, (H, rows, D), jnp.bfloat16) for kk in ks)
        w_arr = jnp.full((1, fa.LANES), float(W), jnp.float32)
        bq, bk = fa._auto_block(rows, fa.MAX_BLOCK_Q), fa._auto_block(rows, fa.MAX_BLOCK_K)
        whole = lambda q, k, v, w: fa._flash_forward(q, k, v, None, w, D ** -0.5, True, bq, bk, False)[0]
        row = {}
        row["whole_grid_ms"], ref = timeit(whole, q, k, v, w_arr)
        for block_q, block_k, heads, unroll in BANDS:
            band = lambda q, k, v: fa._band_forward(q, k, v, None, D ** -0.5, block_q, block_k, W,
                                                    False, with_lse=False, heads=heads, unroll=unroll)[0]
            ms, out = timeit(band, q, k, v)
            err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32))))
            row[f"band_{block_q}_{block_k}_h{heads}_u{unroll}_ms"] = ms
            row[f"band_{block_q}_{block_k}_h{heads}_u{unroll}_maxdiff"] = err
        auto = lambda q, k, v: fa._flash_forward(q, k, v, None, w_arr, D ** -0.5, True,
                                                 *fa._band_blocks(rows, W), False, band=W)[0]
        row["band_auto_with_lse_ms"], _ = timeit(auto, q, k, v)
        serving = lambda q, k, v: fa._flash_forward(q, k, v, None, w_arr, D ** -0.5, True,
                                                    *fa._band_blocks(rows, W), False, band=W,
                                                    with_lse=False)[0]
        row["band_auto_ms"], _ = timeit(serving, q, k, v)
        table[rows] = row
        print(rows, json.dumps(row), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/flash_band_blocks.json", "w") as f:
        json.dump(table, f, indent=1)


if __name__ == "__main__":
    main()
