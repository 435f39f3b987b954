"""The decode kernel ALONE on one chip, at the two cells' shapes (PR 58): a program that calls
it once a cache layer over a whole stack, as a model's layer loop does, under several length
mixes and, for this tree's kernel, several blocks (``rule``: ``block_rows``' own).

    ouro    [48, 24, 1024, 16, 128] bfloat16, 48 calls a step (ouro-2.6b-L12.serve-reason)
    olmoe   [ 4, 16, 2048, 16, 128] bfloat16,  4 calls a step (olmoe-1b-7b-L4.serve-doc)

Mixes: ``cell`` (Ouro: a prompt of the cell's lognormal plus a uniform share of an output of
256-512, ~430 live a row; OLMoE: uniform 780-2,044, ~1,412 live), ``all500`` and ``all520``
(every row just under and just over 512: the parent's one block a row against two), ``full``.
A time is the DEVICE's: the mean duration of the kernel's own events in a profiler trace. The
table also fits time = a x live blocks + b x rows over a variant's readings (us a block, us a
row), which is what ISSUE 58 asked to check first, and prints how far the kernel lies from
``xla_attention`` in float32 on layer 0. PERF.md section 6 (PR 58) has the table.

    git archive <parent> | tar -x -C .chipbench_tree      # once, for the ``parent`` rows
    chiprun -- python3 experiments/decode_kernel_chip.py [--tiny] [--only ouro] [--blocks 128 256]
"""
from __future__ import annotations

import argparse
import glob
import importlib.util
import json
import os
import sys
import tempfile
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.transformer import xla_attention
from deepspeed_tpu.ops.pallas import decode_attention as da

HBM_BYTES_S = 819e9  # one v5e chip (chipbench/peaks.json)
REPS = 4
PARENT = os.path.join(ROOT, ".chipbench_tree", "deepspeed_tpu", "ops", "pallas", "decode_attention.py")
SHAPES = {"ouro": (48, 24, 1024, 16, 128), "olmoe": (4, 16, 2048, 16, 128)}
TINY = {"ouro": (3, 4, 256, 4, 32), "olmoe": (2, 3, 512, 4, 32)}


def load_parent():
    if not os.path.exists(PARENT):
        return None
    spec = importlib.util.spec_from_file_location("deepspeed_tpu.ops.pallas.decode_attention_parent",
                                                  PARENT)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def lengths(name, mix, rows, smax, seed=18):
    """-> int32 [rows]: the newest valid position of each row."""
    rng = np.random.default_rng(seed)
    if mix == "cell" and name == "ouro":
        prompt = np.clip(np.exp(rng.normal(np.log(192 / 1024 * smax), 0.6, rows)),
                         96 / 1024 * smax, smax // 2)
        live = prompt + rng.uniform(0, 1, rows) * rng.uniform(smax // 4, smax // 2, rows)
    elif mix == "cell":
        live = rng.uniform(780 / 2048 * smax, 2044 / 2048 * smax, rows)
    else:
        live = np.full(rows, {"all500": 500, "all520": 520, "full": smax}[mix])
    return (np.clip(live, 1, smax) - 1).astype(np.int32)


def program(module, layers, parent):
    """One call a cache layer over the stacks, the work list built once outside the loop."""
    def step(q, ks, vs, pos):
        kw = {}
        if not parent:
            kw["walk"] = module.decode_walk(pos, q.shape[0], ks.shape[2], module.block_rows(
                ks.shape[2], ks.shape[3] * ks.shape[4] * ks.dtype.itemsize))

        def body(acc, l):
            return acc + module.decode_attention(q, ks, vs, pos, layer=l, **kw).astype(jnp.float32), None

        return jax.lax.scan(body, jnp.zeros(q.shape, jnp.float32),
                            jnp.arange(layers, dtype=jnp.int32))[0]
    return jax.jit(step)


def kernel_us(fn, args, interpret):
    """-> us a call of the kernel, from the device's own events (None on the CPU), and the output."""
    from chipbench import reduce

    out = jax.block_until_ready(fn(*args))
    if interpret:
        return None, out
    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            for _ in range(REPS):
                out = fn(*args)
            jax.block_until_ready(out)
        path, = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
        ops, = reduce.load(path).devices.values()
        spent = [b - a for name, a, b in ops if "decode_attention" in name]
    return 1e6 * sum(spent) / len(spent), out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true", help="a CPU rehearsal of this script")
    ap.add_argument("--only", nargs="*", help="shapes to run (default: both)")
    ap.add_argument("--blocks", nargs="*", type=int, default=[128, 256, 512])
    ap.add_argument("--mixes", nargs="*", default=["cell", "all500", "all520", "full"])
    args = ap.parse_args()
    interpret = jax.default_backend() != "tpu"
    assert args.tiny or not interpret, "times come from the chip: --tiny rehearses on the CPU"
    print(jax.devices(), flush=True)
    parent = load_parent()
    table = {}
    for name, (layers, rows, smax, heads, width) in (TINY if args.tiny else SHAPES).items():
        if args.only and name not in args.only:
            continue
        ks = jax.random.split(jax.random.PRNGKey(rows), 3)
        q = jax.random.normal(ks[0], (rows, heads, width), jnp.bfloat16)
        stack = jax.jit(lambda x: jnp.broadcast_to(x[None], (layers,) + x.shape) * jnp.bfloat16(1))
        k_stack, v_stack = (stack(jax.random.normal(kk, (rows, smax, heads, width), jnp.bfloat16))
                            for kk in ks[1:])
        token_bytes = 2 * heads * width * 2  # K and V of one cached position
        reference = jax.jit(lambda q, k, v, p: layers * xla_attention(  # every layer holds layer 0
            q[:, None].astype(jnp.float32), k[0].astype(jnp.float32), v[0].astype(jnp.float32),
            causal_offset=p)[:, 0])
        variants = ([("parent", None)] if parent else []) + [("rule", None)] + [
            (f"block{b}", b) for b in args.blocks if b <= smax]
        rows_out = {}
        for label, block in variants:
            module = parent if label == "parent" else da
            rule = (lambda s, b: block) if block else da.block_rows
            readings = []
            with mock.patch.object(da, "block_rows", rule):
                walked = min(512, smax) if label == "parent" else da.block_rows(smax, token_bytes // 2)
                fn = program(module, layers, label == "parent")
                for mix in args.mixes:
                    pos = lengths(name, mix, rows, smax)
                    us, out = kernel_us(fn, (q, k_stack, v_stack, jnp.asarray(pos)), interpret)
                    want = reference(q, k_stack, v_stack, pos)
                    live = int(np.sum(pos + 1))
                    blocks = int(np.sum(pos // walked + 1))
                    row = {"block": walked, "live": live, "fetched_over_live": blocks * walked / live,
                           "live_blocks": blocks, "us": us,
                           "max_abs_err": float(jnp.max(jnp.abs(out - want))) / layers}
                    if us:
                        row["live_gb_s"] = live * token_bytes / us / 1e3
                        row["roofline_pct"] = 100 * live * token_bytes / HBM_BYTES_S / (us / 1e6)
                    readings.append(row)
                    print(name, label, mix, json.dumps(row), flush=True)
            fit = None
            if all(r["us"] for r in readings) and len(readings) >= 2:
                a = np.array([[r["live_blocks"], rows] for r in readings], float)
                (per_block, per_row), *_ = np.linalg.lstsq(a, np.array([r["us"] for r in readings]),
                                                           rcond=None)
                fit = {"us_a_block": per_block, "us_a_row": per_row}
                print(name, label, "fit", json.dumps(fit), flush=True)
            rows_out[label] = {"readings": dict(zip(args.mixes, readings)), "fit": fit}
        table[name] = {"shape": [layers, rows, smax, heads, width], "variants": rows_out}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out_name = "decode_kernel_chip.rehearsal.json" if args.tiny else "decode_kernel_chip.json"
    with open(os.path.join(ROOT, "chiprun_out", out_name), "w") as f:
        json.dump(table, f, indent=1)


if __name__ == "__main__":
    main()
