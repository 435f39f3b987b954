"""Collective micro-benchmarks over the device mesh.

Reference: ``benchmarks/communication/run_all.py`` + per-collective scripts
(all_reduce.py, all_gather.py, all_to_all.py, broadcast.py, pt2pt.py).

Each collective is exercised the way the framework actually runs it: traced
over a named mesh axis inside a jitted ``shard_map`` program, so the numbers
include XLA's codegen for the collective (on real hardware, ICI traffic; on
the CPU fake mesh, a functional smoke + relative comparison).
"""

from __future__ import annotations

import argparse
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .utils import report_line, time_fn

OPS = ("all_reduce", "all_gather", "reduce_scatter", "all_to_all", "broadcast", "pt2pt")


def _mesh() -> Mesh:
    devs = np.asarray(jax.devices())
    return Mesh(devs, ("x",))


def build_op(op: str, mesh: Mesh, shape):
    """Return a jitted fn taking an 'x'-sharded array."""
    spec = P("x")
    rep = P()

    def wrap(body, in_spec, out_spec):
        # replication of collective outputs isn't statically inferrable
        return jax.jit(shard_map(body, mesh=mesh, in_specs=(in_spec,),
                                 out_specs=out_spec, check_vma=False))

    if op == "all_reduce":
        return wrap(lambda x: lax.psum(x, "x"), spec, spec)
    if op == "all_gather":
        return wrap(lambda x: lax.all_gather(x, "x", tiled=True), spec, rep)
    if op == "reduce_scatter":
        return wrap(lambda x: lax.psum_scatter(x, "x", tiled=True), rep, spec)
    if op == "all_to_all":
        n = mesh.shape["x"]

        def a2a(x):  # local [1, C]: send C/n elements to each peer
            C = x.shape[-1]
            chunks = x.reshape(n, C // n)
            out = lax.all_to_all(chunks, "x", split_axis=0, concat_axis=0)
            return out.reshape(x.shape)

        return wrap(a2a, spec, spec)
    if op == "broadcast":
        # one-to-all: implemented as select + psum (rank-0 contributes)
        def bcast(x):
            idx = lax.axis_index("x")
            return lax.psum(jnp.where(idx == 0, x, jnp.zeros_like(x)), "x")

        return wrap(bcast, spec, spec)
    if op == "pt2pt":
        n = mesh.shape["x"]
        perm = [(i, (i + 1) % n) for i in range(n)]
        return wrap(lambda x: lax.ppermute(x, "x", perm), spec, spec)
    raise ValueError(op)


def run(op: str, mesh: Mesh, nbytes: int, dtype=jnp.float32) -> str:
    n = mesh.shape["x"]
    # multiple of n*n: the per-device [1, C] shard must split C into n chunks
    # for all_to_all, so C % n == 0 i.e. elems % n*n == 0
    elems = max(n * n, nbytes // jnp.dtype(dtype).itemsize)
    elems = (elems // (n * n)) * (n * n)
    x = jnp.arange(elems, dtype=dtype).reshape(n, -1)
    x = jax.device_put(x, NamedSharding(mesh, P("x")))
    fn = build_op(op, mesh, x.shape)
    secs = time_fn(fn, x)
    return report_line(op, elems * jnp.dtype(dtype).itemsize, secs, n)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description="dstpu collective benchmarks")
    p.add_argument("--ops", nargs="*", default=list(OPS), choices=OPS)
    p.add_argument("--minsize", type=int, default=1 << 20)
    p.add_argument("--maxsize", type=int, default=1 << 26)
    p.add_argument("--dtype", default="float32", choices=("float32", "bfloat16"))
    args = p.parse_args(argv)
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32

    mesh = _mesh()
    print(f"mesh: {mesh.shape} on {jax.devices()[0].platform}")
    for op in args.ops:
        size = args.minsize
        while size <= args.maxsize:
            print(run(op, mesh, size, dtype))
            size *= 4
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
