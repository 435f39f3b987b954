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

    chiprun -- python experiments/grouped_gemm_tiles.py [--buffers]

``--buffers`` (PR 62) times the rule's tiling alone at two and at three blocks of the bank kept
in VMEM. ``--block`` (PR 62) times the routed BLOCK whole at the few rows of a decode step (the route
excluded: the sort, the gathers, the three products, the combine), ``experts_dense`` against
``experts_sorted`` through the kernel at several row tiles and bank buffers, on held two-layer
stacks at the four whole-bank decode shapes of the benchmark, the choices drawn so that a step
touches the experts the cells' spans report (``experts_touched``). Calls are chained inside one
program and every call's choices are the drawn ones shifted by the call's number, so nothing of
the sorted form is the same from call to call (the compiler would lift it out of the loop). The
rule ``moe/dropless.py::expert_gemm_form`` decides the few-rows end by is read off this table
(PERF.md section 6, PR 62).

    chiprun -- python experiments/grouped_gemm_tiles.py --block [kanana olmoe ...] [--tiny]
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

from deepspeed_tpu.moe import dropless
from deepspeed_tpu.moe.dropless import held_chunk_rows
from deepspeed_tpu.ops.pallas import grouped_gemm
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


# name: (rows of a decode step, choices a row, experts, model width, expert width, layers held,
# experts a step touches a layer: the decode spans' ``experts_touched`` in the cell, PERF.md)
BLOCKS = {
    "kanana": (24, 6, 128, 2048, 768, 2, 84.5),
    "kanana-6-layers": (24, 6, 128, 2048, 768, 6, 84.5),  # the cell's own stacks: 768 groups
    "olmoe": (16, 8, 64, 2048, 1024, 2, 55.75),
    "mellum2": (32, 8, 64, 2304, 896, 2, 62.5),
    "lfm2": (128, 4, 64, 2048, 1536, 2, 64.0),
}
TINY = {"kanana": (24, 6, 16, 128, 128, 2, 14.0), "lfm2": (128, 4, 8, 128, 256, 2, 8.0)}


def draw_choices(rng, rows: int, k: int, experts: int, touched: float):
    """[rows, k] distinct experts a row whose union is about ``touched`` experts: every expert a
    popularity exp(s z), a row's k the top of popularity + Gumbel noise, s by bisection on the
    mean of 16 draws (s = 0 is the even router; a trained or a seeded one is more uneven)."""
    z = rng.standard_normal(experts)

    def draw(s):
        return np.argsort(-(s * z + rng.gumbel(size=(rows, experts))), axis=1)[:, :k]

    lo, hi = 0.0, 4.0
    for _ in range(12):
        s = 0.5 * (lo + hi)
        mean = np.mean([len(np.unique(draw(s))) for _ in range(16)])
        lo, hi = (s, hi) if mean > touched else (lo, s)
    return draw(lo).astype(np.int32)


def time_block(form, bank, x, weights, experts, layer, n=3):
    """ms a call of ``form(bank, x, weights, experts, layer)`` -> [T, M], and one call's output."""
    E = bank["wi"].shape[1]

    def chained(bank, x, weights, experts, layer):
        def body(i, x):
            out = form(bank, x, weights, (experts + i) % E, layer)
            return lax.dynamic_update_slice(x, out[:8, :128] * 1e-3, (0, 0))
        return lax.fori_loop(0, INNER, body, x)

    many = jax.jit(chained)
    out = jax.jit(form)(bank, x, weights, experts, layer)
    many(bank, x, weights, experts, layer).block_until_ready()
    best = float("inf")
    for _ in range(n):
        t = time.perf_counter()
        many(bank, x, weights, experts, layer).block_until_ready()
        best = min(best, (time.perf_counter() - t) / INNER)
    return best * 1e3, out


def sorted_with(tm: int, buffers: int):
    """``experts_sorted`` through the kernel at a row tile of ``tm`` and ``buffers`` bank blocks:
    the module's two decisions set for the length of the trace."""
    def form(bank, x, weights, experts, layer):
        was = grouped_gemm.ROW_TILE, grouped_gemm.bank_blocks
        grouped_gemm.ROW_TILE, grouped_gemm.bank_blocks = tm, (lambda m: buffers)
        try:
            return dropless.experts_sorted(bank, x, weights, experts, layer, kernel=True)
        finally:
            grouped_gemm.ROW_TILE, grouped_gemm.bank_blocks = was
    return form


def sorted_without_products(bank, x, weights, experts, layer):
    """The sorted form with its three products taken out (a slice or a tiling of the rows in
    their place): the sort, the count, the gathers and the combine."""
    def stand_in(xs, w, sizes, kernel):
        reps = -(-w.shape[2] // xs.shape[1])
        return jnp.tile(xs, (1, reps))[:, :w.shape[2]] * sizes[-1].astype(xs.dtype)
    was = dropless._grouped_dot
    dropless._grouped_dot = stand_in
    try:
        return dropless.experts_sorted(bank, x, weights, experts, layer, kernel=False)
    finally:
        dropless._grouped_dot = was


def blocks(only, tiny: bool):
    table = {}
    for name, (T, k, E, M, F, L, touched) in (TINY if tiny else BLOCKS).items():
        if only and name not in only:
            continue
        rng = np.random.default_rng(62 + T + E)
        experts = draw_choices(rng, T, k, E, touched)
        ks = jax.random.split(jax.random.PRNGKey(T + F), 5)
        bank = {"wg": jax.random.normal(ks[0], (L, E, M, F), jnp.bfloat16) * M ** -0.5,
                "wi": jax.random.normal(ks[1], (L, E, M, F), jnp.bfloat16) * M ** -0.5,
                "wo": jax.random.normal(ks[2], (L, E, F, M), jnp.bfloat16) * F ** -0.5}
        x = jax.random.normal(ks[3], (T, M), jnp.bfloat16)
        weights = jax.nn.softmax(jax.random.normal(ks[4], (T, k), jnp.float32), axis=-1)
        layer = jnp.int32(L - 1)
        n_touched = len(np.unique(experts))
        bank_mb = 3 * M * F * 2 / 1e6
        row = {"rows": T, "k": k, "experts": E, "M": M, "F": F, "layers": L,
               "touched": n_touched, "even_router": E * (1 - (1 - k / E) ** T),
               "dense_floor_ms": E * bank_mb * 1e6 / PEAK_BYTES * 1e3,
               "touched_floor_ms": n_touched * bank_mb * 1e6 / PEAK_BYTES * 1e3}
        experts = jnp.asarray(experts)
        args = (bank, x, weights, experts, layer)
        row["dense_ms"], ref = time_block(dropless.experts_dense, *args)
        ref = np.asarray(ref, np.float32)
        print(name, json.dumps(row), flush=True)
        forms = {"sorted_no_products": sorted_without_products,
                 "sorted_ragged_dot": lambda *a: dropless.experts_sorted(*a, kernel=False)}
        for tm in ((16, 128) if tiny else (16, 32, 64, 128)):
            for buffers in (2, 3):
                forms[f"gmm{tm}_x{buffers}"] = sorted_with(tm, buffers)
        for key, form in forms.items():
            try:
                ms, out = time_block(form, *args)
            except Exception as e:  # noqa: BLE001 -- a tiling the compiler refuses is a row of the table
                row[key] = str(e).splitlines()[0][:160]
                print(name, key, row[key], flush=True)
                continue
            row[key + "_ms"] = ms
            if key != "sorted_no_products":
                row[key + "_maxdiff"] = float(np.max(np.abs(np.asarray(out, np.float32) - ref)))
            print(name, key, round(ms, 4), round(ms / row["dense_ms"], 3),
                  row.get(key + "_maxdiff"), flush=True)
        table[name] = row
        del bank, args
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/grouped_gemm_blocks.json", "w") as f:
        json.dump(table, f, indent=1)
    print("| shape | rows x k / E | touched | dense ms | best sorted ms (form) | sorted / dense | "
          "no products ms | touched banks at 819 GB/s ms |\n|---|---|---|---|---|---|---|---|")
    for name, row in table.items():
        timed = {key[:-3]: ms for key, ms in row.items()
                 if key.startswith("gmm") and key.endswith("_ms")}
        best = min(timed, key=timed.get) if timed else None
        print("| %s | %d x %d / %d | %d | %.3f | %s | %s | %.3f | %.3f |" % (
            name, row["rows"], row["k"], row["experts"], row["touched"], row["dense_ms"],
            "%.3f (%s)" % (timed[best], best) if best else "-",
            "%.3f" % (timed[best] / row["dense_ms"]) if best else "-",
            row.get("sorted_no_products_ms", float("nan")), row["touched_floor_ms"]))


def main():
    print(jax.devices(), flush=True)
    only = [a for a in sys.argv[1:] if not a.startswith("--")]
    if "--block" in sys.argv:
        return blocks(only, "--tiny" in sys.argv)
    both = "--buffers" in sys.argv  # the rule's tiling alone, at two and at three bank blocks
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
        for t in ([(*gmm_tiling(m, K, N), b) for b in (2, 3)] if both else tilings(m, K, N)):
            key = "gmm_" + "_".join(str(v) for v in t)
            if both:
                grouped_gemm.bank_blocks = lambda m, blocks=t[2]: blocks
            try:
                ms, out = timeit(lambda l, r, s: grouped_matmul(l, r, s, t[:2]), lhs, rhs, sizes)
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
