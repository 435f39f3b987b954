"""The causal flash kernels alone on one chip, at the cells' shapes, under each schedule a causal
grid step can take (PR 51): what the kernels of the PARENT commit run (every block the diagonal
touches, whole, and a fetch for every step), and this tree's kernels with and without the cut of a
step's block to the key sub-tiles its rows see (``block@512x1024``: no cut, the parent's work
under this tree's index maps, which fetch nothing for a step that computes nothing) at several
OUTER blocks (``512``: the module's own), the backward as the ONE kernel ``flash_bwd`` where
``backward_form`` takes it (PR 64) and, under ``/split``, as the pair that recomputes the scores. A time is
the DEVICE's: the mean duration of the kernel's own events in a profiler trace of ``REPS`` calls,
so no host time and no neighbouring operation is in it. ``of_triangle_pct`` is the share of the
chip's peak the call reaches when charged the triangle's operations alone (``chipbench/flops.py``
``flash_cost``'s count: 2 matmuls forward, 5 backward, half the square), which is what
``flash_roofline_pct`` reads in the train cell. PERF.md section 6 (PR 51) has the table, and the
two forms it refused (a loop over sub-tiles; a mask only on the sub-tiles the diagonal crosses);
PR 64's has both backward forms at the train cell's shape, at 1,536 and 4,096 rows, at latent
attention's 192 / 128 heads (which the parent's backward refuses: its row says so) and under alibi.

    git archive <parent> | tar -x -C .chipbench_tree      # once, for the ``parent`` column
    chiprun -- python3 experiments/flash_causal_blocks.py [--tiny] [--only train] [--schedules 512 512/split]

Without a parent tree the ``parent`` column is left out; ``--beside LABEL=PATH`` adds a column
of another tree's ``flash_attention.py`` (PR 65: the first round's kernel, which made ``delta``
inside the step). ``xla_beside``: what XLA ran beside the kernels in a call, the pass over dO
and O that makes ``delta``'s row (and, at the parent, the two lane-broadcast arrays).
"""
from __future__ import annotations

import argparse
import glob
import importlib.util
import json
import os
import re
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas import flash_attention as fa

PEAK_FLOPS = 197e12  # one v5e chip, bf16 (chipbench/peaks.py)
REPS = 10
# kernel: what finds its events by name. ``flash_bwd_dkdv`` + ``flash_bwd_dq`` are the parent's
# backward and this tree's ``split`` form, ``flash_bwd`` (PR 64) the one kernel
KERNELS = {"flash_fwd": "flash_fwd", "flash_bwd_dkdv": "flash_bwd_dkdv", "flash_bwd_dq": "flash_bwd_dq",
           "flash_bwd": r"flash_bwd(?!_d)"}
BACKWARD = ("flash_bwd_dkdv", "flash_bwd_dq", "flash_bwd")
PARENT = os.path.join(ROOT, ".chipbench_tree", "deepspeed_tpu", "ops", "pallas", "flash_attention.py")
# name: (fused batch x heads, rows, q/k width, value width, alibi, with the backward[, mask_block])
SHAPES = {
    "train": (128, 2048, 128, 128, False, True),       # pythia-1.4b.train-zero3-x4: 8 x 16 heads
    "train-1024": (128, 1024, 128, 128, False, True),
    "train-1536": (128, 1536, 128, 128, False, True),
    "train-4096": (64, 4096, 128, 128, False, True),
    "train-6144": (32, 6144, 128, 128, False, True),    # the longest rows whose dQ stays in VMEM
    "latent-train": (64, 2048, 192, 128, False, True),  # latent attention's heads (kanana's kind)
    "alibi-train": (64, 2048, 128, 128, True, True),    # BLOOM's kind
    "bloom": (16, 2048, 128, 128, True, False),        # bloom-1b7.serve-doc's prefill
    "kanana-8192": (32, 8192, 192, 128, False, False),  # latent attention, expanded
    "kanana-4096": (32, 4096, 192, 128, False, False),
    "k-exaone-8192": (64, 8192, 128, 128, False, False),
    # the shortest buckets that attend through the kernel (``cache_attention_form``): the
    # forward runs their diagonal whole (a key block under four query blocks)
    "k-exaone-1024": (64, 1024, 128, 128, False, False),
    "lfm2-1024": (32, 1024, 64, 64, False, False),
    "sdar-1024": (32, 1024, 128, 128, False, False, 4),  # causal between blocks of 4 positions
    "sdar-2048": (32, 2048, 128, 128, False, False, 4),
}
TINY = {"train": (2, 2048, 32, 32, False, True), "bloom": (2, 1024, 32, 32, True, False)}
# name: (SUB_K (None: the whole block), (MAX_BLOCK_Q, MAX_BLOCK_K) (None: the module's)[, the
# backward's form (PR 64; left out: ``backward_form``'s own)[, the diagonal's sub-tile edge
# (PR 65; left out: ``DIAG_SUB``; 0: the diagonal's tile whole, which leaves of PR 65 the
# transposed backward tile and the row statistics as rows)]])
SCHEDULES = {"512": (512, None), "512/split": (512, None, "split"),
             "512/whole-diagonal": (512, None, None, 0),
             "512/split/whole-diagonal": (512, None, "split", 0),
             "512@512x1024": (512, (512, 1024)), "512@512x1024/split": (512, (512, 1024), "split"),
             "512@256x2048": (512, (256, 2048)), "512@1024x1024": (512, (1024, 1024)),
             "512@512x512": (512, (512, 512)), "block@512x1024": (None, (512, 1024))}


def load_beside(label, path):
    """Another tree's kernels, a module of its own beside this tree's: the ``parent`` column."""
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location(
        f"deepspeed_tpu.ops.pallas.flash_attention_{re.sub(r'[^0-9a-z]', '_', label)}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class schedule:
    """The module's constants for the length of a trace: the kernels read them as they are traced."""

    NAMES = ("SUB_K", "MAX_BLOCK_Q", "MAX_BLOCK_K", "FUSED_VMEM_BYTES", "DIAG_SUB")

    def __init__(self, sub_k, blocks, form=None, diag=None):
        budget = {None: fa.FUSED_VMEM_BYTES, "split": 0, "fused": 1 << 40}[form]
        self.new = (sub_k or 1 << 30, *(blocks or (fa.MAX_BLOCK_Q, fa.MAX_BLOCK_K)), budget,
                    fa.DIAG_SUB if diag is None else diag or 1 << 30)

    def __enter__(self):
        self.old = tuple(getattr(fa, name) for name in self.NAMES)
        self.set(self.new)

    def __exit__(self, *exc):
        self.set(self.old)

    def set(self, values):
        for name, value in zip(self.NAMES, values):
            setattr(fa, name, value)
        fa.causal_tiles_pct.cache_clear()


def kernel_ms(fn, args, interpret):
    """-> {kernel: ms a call} from the device's own events (``wall``: the whole call by the
    host's clock, where the trace names no kernel: a CPU rehearsal)."""
    from chipbench import reduce

    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as tmp:
        t = time.perf_counter()
        with jax.profiler.trace(tmp):
            for _ in range(REPS):
                out = fn(*args)
            jax.block_until_ready(out)
        wall = (time.perf_counter() - t) / REPS * 1e3
        found = {}
        if not interpret:
            path, = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
            ops, = reduce.load(path).devices.values()
            for kernel, named in KERNELS.items():
                spent = [b - a for name, a, b in ops if re.search(named, name)]
                if spent:  # one event a call: ``events`` says so
                    found[kernel] = sum(spent) / REPS * 1e3
                    found.setdefault("events", {})[kernel] = len(spent)
            # what XLA runs beside the kernels in the call (the pass that makes ``delta``)
            beside = [b - a for name, a, b in ops if not re.search("flash_", name)]
            found["xla_beside"] = sum(beside) / REPS * 1e3
    return found or {"wall": wall}, out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true", help="a CPU rehearsal of this script")
    ap.add_argument("--only", nargs="*", help="shapes to run (default: all)")
    ap.add_argument("--schedules", nargs="*", help="schedules to run beside the parent (default: all)")
    ap.add_argument("--beside", nargs="*", default=[], metavar="LABEL=PATH",
                    help="more columns like the parent's: another tree's flash_attention.py")
    args = ap.parse_args()
    interpret = jax.default_backend() != "tpu"
    assert args.tiny or not interpret, "times come from the chip: --tiny rehearses on the CPU"
    print(jax.devices(), flush=True)
    beside = {label: load_beside(label, path) for label, path in
              [("parent", PARENT)] + [item.split("=", 1) for item in args.beside]}
    beside = {label: module for label, module in beside.items() if module}
    table = {}
    for name, (bh, rows, d, dv, alibi, backward, *mask) in (TINY if args.tiny else SHAPES).items():
        if args.only and name not in args.only:
            continue
        mask_block = mask[0] if mask else 1
        ks = jax.random.split(jax.random.PRNGKey(rows), 4)
        q, k = (jax.random.normal(kk, (bh, rows, d), jnp.bfloat16) for kk in ks[:2])
        v, g = (jax.random.normal(kk, (bh, rows, dv), jnp.bfloat16) for kk in ks[2:])
        slopes = (jnp.broadcast_to(2.0 ** -(1 + jnp.arange(bh) % 8)[:, None, None].astype(jnp.float32),
                                   (bh, 1, fa.LANES)) if alibi else None)
        auto = lambda m=fa: (m._auto_block(rows, m.MAX_BLOCK_Q),
                             m._key_block(rows, max(d, dv), 2) if hasattr(m, "_key_block")
                             else m._auto_block(rows, m.MAX_BLOCK_K))
        triangle = bh * rows * rows / 2
        counted = {"flash_fwd": 2 * triangle * (d + dv), "backward": 2 * triangle * (3 * d + 2 * dv)}

        def call(module, blocks):  # arrays are operands: a closed-over one is baked into the executable
            attend = lambda q, k, v, slopes: module._flash_bhsd(
                q, k, v, slopes, None, d ** -0.5, True, *blocks, interpret, 0, mask_block)
            if not backward:
                return jax.jit(lambda q, k, v, slopes, g: attend(q, k, v, slopes))
            return jax.jit(lambda q, k, v, slopes, g: jax.vjp(
                lambda q, k, v: attend(q, k, v, slopes), q, k, v)[1](g))

        rows_out, ref = {}, None
        variants = [(label, None) for label in beside] + [
            item for item in SCHEDULES.items() if not args.schedules or item[0] in args.schedules]
        for label, sched in variants:
            module = beside[label] if sched is None else fa
            with schedule(*(sched or (fa.SUB_K, None))):
                blocks = auto(module)
                try:
                    ms, out = kernel_ms(call(module, blocks), (q, k, v, slopes, g), interpret)
                # a block the chip's compiler refuses is a row, and so is a backward the parent lacks
                except Exception as e:  # noqa: BLE001
                    print(name, label, blocks, "refused:", str(e)[-300:], flush=True)
                    continue
                tiles = form = None
                if sched is not None:
                    tiles = fa.causal_tiles_pct(rows, max(d, dv), 2)
                    form = fa.backward_form(rows, d, dv, 2, *blocks) if backward else None
            ref = out if ref is None else ref
            row = {"blocks": blocks, "ms": ms, "causal_tiles_pct": tiles, "backward_form": form,
                   "maxdiff_vs_first": max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                                                 - b.astype(jnp.float32))))
                                           for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)))}
            if "flash_fwd" in ms:
                share = lambda ops, t: 100 * ops / PEAK_FLOPS / (t / 1e3)
                row["of_triangle_pct"] = {"flash_fwd": share(counted["flash_fwd"], ms["flash_fwd"])}
                if backward:
                    both = sum(ms.get(kernel, 0.0) for kernel in BACKWARD)
                    row["backward_ms"] = both
                    row["of_triangle_pct"]["backward"] = share(counted["backward"], both)
                    row["of_triangle_pct"]["all"] = share(sum(counted.values()), both + ms["flash_fwd"])
            rows_out[label] = row
            print(name, label, json.dumps(row), flush=True)
        table[name] = {"shape": [bh, rows, d, dv], "alibi": alibi, "blocks": auto(),
                       "schedules": rows_out}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    name = "flash_causal_blocks.rehearsal.json" if args.tiny else "flash_causal_blocks.json"
    with open(os.path.join(ROOT, "chiprun_out", name), "w") as f:
        json.dump(table, f, indent=1)


if __name__ == "__main__":
    main()
