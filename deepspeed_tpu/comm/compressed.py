"""Compressed collective backend — standalone 1-bit error-feedback allreduce.

Reference: ``runtime/comm/nccl.py:51`` ``NcclBackend.compressed_allreduce``
(and ``mpi.py:170``): sign-compress a worker's tensor with an error-feedback
residual, allreduce the 1-bit payload + per-tensor scale, return the dense
average — the comm kernel under the 1-bit optimizers, also usable directly.

TPU-native: the compression is elementwise math and the 1-bit transport is a
TRUE bit-packed payload — signs packed 8-per-uint8-byte (reference
nccl.py:76-82 packs into cupy uint8 the same way) shipped with one fp32 scale
per tensor via ``lax.all_gather`` over the mesh axis; every rank unpacks and
averages locally in fp32. The wire carries n/8 + 4 bytes for n values — 32x
less than the fp32 gradient psum it replaces. The function is written for use
INSIDE ``shard_map`` (per-device view, like the reference's per-rank code);
``compressed_allreduce`` is the convenience wrapper that builds the shard_map
for host-level callers.
"""

from __future__ import annotations

from typing import Sequence, Union

import jax
import jax.numpy as jnp

from .collectives import all_gather, all_to_all

Axes = Union[str, Sequence[str]]


def pack_signs(x: jax.Array) -> jax.Array:
    """Flatten ``x`` and pack its sign bits little-endian, 8 per uint8 byte.

    Bit = 1 iff value >= 0 — matching the reference's ``sign().add_(1).bool()``
    (nccl.py:76), under which exact zero transmits as +1."""
    bits = (x.reshape(-1) >= 0).astype(jnp.uint8)
    return jnp.packbits(bits, bitorder="little")  # [ceil(n/8)] uint8


def unpack_signs(packed: jax.Array, n: int) -> jax.Array:
    """Inverse of :func:`pack_signs` along the last axis: uint8 bytes -> ±1
    fp32 values. ``packed`` may carry leading axes (e.g. a [world] gather)."""
    bits = jnp.unpackbits(packed, axis=-1, count=n, bitorder="little")
    return bits.astype(jnp.float32) * 2.0 - 1.0


def compressed_allreduce_p(tensor: jax.Array, error: jax.Array, axes: Axes):
    """Per-device (inside shard_map): returns (averaged_tensor, new_error).

    ``tensor`` is this rank's local dense value; ``error`` its accumulated
    compression residual (same shape). The 1-bit payload is sign(tensor +
    error) packed to uint8 with one L1 scale per tensor (reference nccl.py:51
    layout: sign bits + scale on the wire, fp32 averaging server-side)."""
    comp = tensor + error
    n = comp.size
    scale = jnp.sum(jnp.abs(comp)) / n
    packed = pack_signs(comp)  # the 1-bit wire: ceil(n/8) uint8 bytes
    # .collectives wrappers so the 1-bit wire lands in the comm byte
    # accounting (the saving the ROADMAP's comm counters measure)
    gathered = all_gather(packed, axes, tiled=False)  # [world, n/8] uint8 on the wire
    scales = all_gather(scale, axes, tiled=False)  # [world] fp32 (4 bytes/rank)
    signs = unpack_signs(gathered, n)  # [world, n] ±1, decompressed locally
    avg = jnp.mean(scales[:, None] * signs, axis=0).reshape(comp.shape)
    # error feedback compensates the payload as TRANSMITTED (scale * ±1 from
    # the packed bits — note sign(0) travels as +1), not the pre-compression
    # value — otherwise the quantization residual leaks every step
    transmitted = (scale * unpack_signs(packed, n)).reshape(comp.shape)
    new_error = comp - transmitted
    return avg, new_error


def compressed_allreduce_2phase_p(tensor: jax.Array, worker_error: jax.Array,
                                  server_error: jax.Array, axes: Axes,
                                  world: int):
    """Per-device two-phase compressed allreduce (the reference's exact
    worker/server scheme, nccl.py:51-140): each rank is the "server" for a
    1/world chunk.

    Phase 1 (worker): compensate with ``worker_error``, compress the WHOLE
    local buffer (one scale), all-to-all so server j receives every rank's
    packed chunk j. Phase 2 (server): decompress, average, compensate with
    ``server_error``, compress AGAIN (one scale per server chunk), all-gather
    the server chunks; every rank decompresses the full result.

    Wire cost per rank: ~2·n/8 bytes, INDEPENDENT of world size — vs the
    one-shot :func:`compressed_allreduce_p` whose all-gather receives
    (world−1)·n/8. The price is a second compression stage (server error
    feedback compensates it across steps, like the reference). n must be
    divisible by ``world * 8`` — every rank's chunk must pack to whole
    bytes (the reference pads to its own corrected size the same way).

    Returns (averaged_tensor, new_worker_error, new_server_error);
    ``server_error`` holds this rank's [n/world] server-chunk residual.
    """
    shape = tensor.shape
    n = tensor.size
    if n % (world * 8) != 0:
        raise ValueError(
            f"2-phase compressed allreduce needs size divisible by "
            f"world*8 = {world * 8}, got {n} — pad the buffer (the reference "
            "pads with a dummy tensor the same way, nccl.py corrected sizes)")
    chunk = n // world
    flat = tensor.reshape(-1)
    # ---- phase 1: worker compression (one scale for the whole buffer) ----
    comp = flat + worker_error.reshape(-1)
    w_scale = jnp.sum(jnp.abs(comp)) / n
    packed = pack_signs(comp)  # [n/8] uint8
    transmitted = w_scale * unpack_signs(packed, n)
    new_worker_error = (comp - transmitted).reshape(shape)
    # server j gets every rank's packed chunk j: all_to_all over the chunk dim
    packed_chunks = packed.reshape(world, chunk // 8)
    recv = all_to_all(packed_chunks, axes, split_axis=0, concat_axis=0,
                      tiled=False)  # [world, chunk/8]: rank r's chunk j=self
    scales = all_gather(w_scale, axes, tiled=False)  # [world] fp32
    # ---- phase 2: server average + re-compression ------------------------
    signs = unpack_signs(recv, chunk)  # [world, chunk]
    avg_chunk = jnp.mean(scales[:, None] * signs, axis=0)  # [chunk]
    comp_s = avg_chunk + server_error
    s_scale = jnp.sum(jnp.abs(comp_s)) / chunk
    packed_s = pack_signs(comp_s)  # [chunk/8]
    transmitted_s = s_scale * unpack_signs(packed_s, chunk)
    new_server_error = comp_s - transmitted_s
    gathered = all_gather(packed_s, axes, tiled=False)  # [world, chunk/8]
    s_scales = all_gather(s_scale, axes, tiled=False)  # [world]
    out = (s_scales[:, None] * unpack_signs(gathered, chunk)).reshape(shape)
    return out, new_worker_error, new_server_error


def _shard_map_per_rank(make_per_device, axis, mesh, n_args, n_outs):
    """Shared wrapper plumbing for the host-level conveniences: shard_map
    ``make_per_device(world)`` over ``axis`` with every arg/output carried
    as [world] per-rank rows except output 0 (the rank-identical average)."""
    from jax.sharding import PartitionSpec as P

    from .mesh import current_mesh

    mesh = mesh if mesh is not None else current_mesh()
    assert mesh is not None, "compressed allreduce needs a mesh"
    world = mesh.shape[axis]
    spec = P(axis)
    fn = jax.shard_map(make_per_device(world), mesh=mesh, in_specs=(spec,) * n_args,
                   out_specs=(P(axis),) + (spec,) * (n_outs - 1))

    def call(*args):
        if args[0].shape[0] != world:
            raise ValueError(
                f"leading world axis {args[0].shape[0]} != mesh axis "
                f"{axis!r} size {world} — each rank's local value must "
                "occupy exactly one row")
        outs = fn(*args)
        # every rank computed the same average; return one copy + per-rank
        # error rows
        return (outs[0][0],) + outs[1:]

    return call


def compressed_allreduce(tensor: jax.Array, error: jax.Array, axis: str = "data",
                         mesh=None):
    """Host-level convenience: shard_map ``compressed_allreduce_p`` over
    ``axis``. ``tensor``/``error`` carry a leading [world] axis holding each
    rank's local value (the per-rank layout the reference sees naturally as
    separate processes)."""

    def make(world):
        def per_device(t, e):
            avg, e_new = compressed_allreduce_p(t[0], e[0], axis)
            return avg[None], e_new[None]

        return per_device

    return _shard_map_per_rank(make, axis, mesh, n_args=2, n_outs=2)(tensor, error)


def compressed_allreduce_2phase(tensor: jax.Array, worker_error: jax.Array,
                                server_error: jax.Array, axis: str = "data",
                                mesh=None):
    """Host-level wrapper for :func:`compressed_allreduce_2phase_p`.

    ``tensor``/``worker_error``: [world, n] per-rank rows;
    ``server_error``: [world, n/world] per-rank server-chunk residuals.
    Returns (avg [n], new_worker_error [world, n], new_server_error
    [world, n/world])."""
    def make(world):
        def per_device(t, we, se):
            avg, we_new, se_new = compressed_allreduce_2phase_p(
                t[0], we[0], se[0], axis, world)
            return avg[None], we_new[None], se_new[None]

        return per_device

    return _shard_map_per_rank(make, axis, mesh, n_args=3, n_outs=3)(
        tensor, worker_error, server_error)


class CompressedBackend:
    """Name-compatible object API (reference NcclBackend/MpiBackend).

    ``two_phase`` selects the reference's worker/server scheme (constant
    ~2·n/8 bytes per rank on the wire, two error buffers) over the one-shot
    gather (single compression stage, (world−1)·n/8 received per rank) —
    the right choice at large world sizes / over DCN."""

    def __init__(self, axis: str = "data", mesh=None, two_phase: bool = False):
        self.axis = axis
        self.mesh = mesh
        self.two_phase = two_phase

    def compressed_allreduce(self, tensor, error, server_error=None,
                             rank=None, world_size=None):
        if self.two_phase:
            assert server_error is not None, "two_phase needs server_error"
            return compressed_allreduce_2phase(
                tensor, error, server_error, axis=self.axis, mesh=self.mesh)
        return compressed_allreduce(tensor, error, axis=self.axis, mesh=self.mesh)
