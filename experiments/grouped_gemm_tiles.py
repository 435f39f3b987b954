"""One projection of the routed feed-forward's grouped matmul on one chip: ``lax.ragged_dot`` (the
compiler's kernel, 512-row tiles) beside ``ops/pallas/grouped_gemm.py`` at several (tm, tn), at the
shapes the benchmark's four routed cells run, the bank held as ``[L * E, K, N]`` with one layer's
groups filled and the groups' sizes drawn as uneven as the cells report
(``moe_load_max_over_mean``). Calls are chained inside ONE program (a corner of the output goes back
into the rows), so no host time is in a figure: ms a call, best of 3 runs of 40, and the share of
the call's roofline (2 operations a parameter a row; the filled groups' matrices read once, the
rows in and out: ``chipbench/moe_cost.py::grouped_gemm_cost``'s count for one projection). The rows
at 256 and 512 tokens a call are the sorted form where ``DENSE_ROWS`` sends the dense one today.
PERF.md section 6 (PR 46) has the table this printed.

    chiprun -- python experiments/grouped_gemm_tiles.py
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from deepspeed_tpu.moe.dropless import held_chunk_rows
from deepspeed_tpu.ops.pallas.grouped_gemm import gmm_tiling, grouped_matmul

INNER = 40
PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9  # chipbench/peaks.json, "TPU v5 lite"

# name: (rows of a call, K, N, experts a layer, routed layers held, max over mean, share of the
# rows that lie in a group: a held chunk is cut a quarter over an even router's share)
CHUNK = held_chunk_rows(2048 * 8, 16, 128)
SHAPES = {
    "olmoe.up": (16384, 2048, 1024, 64, 4, 3.0, 1.0),
    "olmoe.down": (16384, 1024, 2048, 64, 4, 3.0, 1.0),
    "kanana.up": (49152, 2048, 768, 128, 6, 5.3, 1.0),
    "kanana.down": (49152, 768, 2048, 128, 6, 5.3, 1.0),
    "k-exaone.up": (CHUNK, 6144, 2048, 16, 4, 3.0, 0.8),
    "k-exaone.down": (CHUNK, 2048, 6144, 16, 4, 3.0, 0.8),
    "lfm2-1024.up": (4096, 2048, 1536, 64, 8, 2.5, 1.0),
    "lfm2-2048.up": (8192, 2048, 1536, 64, 8, 2.5, 1.0),
    "lfm2-2048.down": (8192, 1536, 2048, 64, 8, 2.5, 1.0),
    # the sorted form at the rows the dense form takes today (DENSE_ROWS)
    "olmoe-256.up": (256 * 8, 2048, 1024, 64, 4, 3.0, 1.0),
    "olmoe-512.up": (512 * 8, 2048, 1024, 64, 4, 3.0, 1.0),
    "lfm2-256.up": (256 * 4, 2048, 1536, 64, 8, 2.5, 1.0),
    "lfm2-512.up": (512 * 4, 2048, 1536, 64, 8, 2.5, 1.0),
}


def draw_sizes(rng, rows: int, experts: int, skew: float):
    """``rows`` pairs over ``experts`` groups whose largest is about ``skew`` x the mean: shares
    exp(s z) of a normal z, s found by bisection, then the pairs dealt by those shares."""
    z = rng.standard_normal(experts)
    lo, hi = 0.0, 4.0
    for _ in range(40):
        s = 0.5 * (lo + hi)
        p = np.exp(s * z)
        lo, hi = (s, hi) if p.max() / p.mean() < skew else (lo, s)
    p = np.exp(lo * z)
    return rng.multinomial(rows, p / p.sum()).astype(np.int32)


def timeit(fn, lhs, rhs, sizes, n=3):
    """-> (ms a call, one call's output). ``fn``: (lhs [m, K], rhs, sizes) -> [m, N]."""

    def chained(lhs, rhs, sizes):
        def body(_, lhs):
            out = fn(lhs, rhs, sizes)
            return lax.dynamic_update_slice(lhs, out[:8, :128] * 1e-3, (0, 0))
        return lax.fori_loop(0, INNER, body, lhs)

    many = jax.jit(chained)
    out = jax.jit(fn)(lhs, rhs, sizes)
    many(lhs, rhs, sizes).block_until_ready()
    best = float("inf")
    for _ in range(n):
        t = time.perf_counter()
        many(lhs, rhs, sizes).block_until_ready()
        best = min(best, (time.perf_counter() - t) / INNER)
    return best * 1e3, out


def tilings(m, K, N):
    rule = gmm_tiling(m, K, N)
    out = [rule]
    for tm in (64, 128, 256, 512):
        for tn in (rule[1], min(512, N)):
            if m % tm == 0 and N % tn == 0 and (tm, tn) not in out:
                out.append((tm, tn))
    return out


def main():
    print(jax.devices(), flush=True)
    only = sys.argv[1:]
    table = {}
    for name, (m, K, N, E, L, skew, filled) in SHAPES.items():
        if only and not any(name.startswith(o) for o in only):
            continue
        rng = np.random.default_rng(46 + m + K)
        layer = L // 2
        sizes = np.zeros((L * E,), np.int32)
        sizes[layer * E:(layer + 1) * E] = draw_sizes(rng, int(m * filled), E, skew)
        ks = jax.random.split(jax.random.PRNGKey(m + N), 2)
        lhs = jax.random.normal(ks[0], (m, K), jnp.bfloat16)
        rhs = jax.random.normal(ks[1], (L * E, K, N), jnp.bfloat16) * K ** -0.5
        sizes = jnp.asarray(sizes)
        live = int(m * filled)
        cost_s = max(2.0 * live * K * N / PEAK_FLOPS,
                     2.0 * (int(np.count_nonzero(sizes)) * K * N + live * (K + N)) / PEAK_BYTES)
        row = {"rows": m, "K": K, "N": N, "groups": L * E, "filled": int(np.count_nonzero(sizes)),
               "max_over_mean": float(sizes.max() / (live / E)), "roofline_ms": cost_s * 1e3,
               "rule": list(gmm_tiling(m, K, N))}
        row["ragged_dot_ms"], ref = timeit(lax.ragged_dot, lhs, rhs, sizes)
        row["ragged_dot_pct"] = 100 * cost_s * 1e3 / row["ragged_dot_ms"]
        print(name, json.dumps(row), flush=True)
        for t in tilings(m, K, N):
            key = "gmm_%d_%d" % t
            try:
                ms, out = timeit(lambda l, r, s: grouped_matmul(l, r, s, t), lhs, rhs, sizes)
            except Exception as e:  # noqa: BLE001 -- a tiling the compiler refuses is a row of the table
                row[key] = str(e).splitlines()[0][:120]
                print(name, key, row[key], flush=True)
                continue
            diff = jnp.abs(out[:live].astype(jnp.float32) - ref[:live].astype(jnp.float32))
            row[key + "_ms"], row[key + "_pct"] = ms, 100 * cost_s * 1e3 / ms
            row[key + "_maxdiff"] = float(jnp.max(diff))
            print(name, key, round(ms, 4), round(row[key + "_pct"], 1), row[key + "_maxdiff"],
                  flush=True)
        table[name] = row
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/grouped_gemm_tiles.json", "w") as f:
        json.dump(table, f, indent=1)


if __name__ == "__main__":
    main()
