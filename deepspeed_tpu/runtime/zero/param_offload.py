"""ZeRO-Infinity parameter tier — host-resident parameters streamed to HBM
per layer inside the compiled step.

Reference: ``runtime/zero/partition_parameters.py:537`` (``zero.Init`` with
``remote_device='cpu'|'nvme'``) + ``runtime/zero/parameter_offload.py`` +
``runtime/swap_tensor/partitioned_param_swapper.py:38`` — the reference keeps
each partitioned parameter's payload in CPU/NVMe storage and swaps it into GPU
memory right before its submodule's forward/backward, so models whose
PARAMETERS exceed device memory train on one device (13B on a 16 GB V100,
docs/_pages/training.md:293).

TPU-native inversion: there are no module hooks and no eager swaps. The whole
parameter pytree lives in PINNED HOST memory (``jax.memory.Space.Host``) and
the model's layer scan streams ONE layer slice at a time into device memory
with ``stream_to_device`` — XLA lowers the transfer to an async
copy-start/copy-done pair and its latency-hiding scheduler overlaps the copy
with compute, which is the role the reference's prefetch coordinator +
separate CUDA streams play. The backward transpose (``_bwd``) pins each
layer's gradient straight back to host, so neither the parameter stack nor
the gradient stack ever materializes in HBM — HBM holds activations plus one
layer's working set.

Tiering composition (engine.py wires these):
  offload_param=cpu  + offload_optimizer=cpu : bf16 params, fp32 masters and
      Adam moments all in host DRAM; update compiled as a
      ``compute_on('device_host')`` region.
  offload_param=nvme + offload_optimizer=nvme: bf16 working set in host DRAM
      (the device must be able to address it), fp32 masters + moments on
      NVMe through the native aio engine (nvme_optimizer.py) — the
      HBM ← DRAM ← NVMe hierarchy of ZeRO-Infinity with the hot tier sized
      2 bytes/param instead of 16.
"""

from __future__ import annotations

from typing import Any

import jax

PyTree = Any


@jax.custom_vjp
def _stream_leaf(x):
    return jax.device_put(x, jax.memory.Space.Device)


def _fwd(x):
    return _stream_leaf(x), None


def _bwd(_, g):
    # gradient goes straight back to host: the [L, ...] cotangent stack the
    # scan transpose assembles must never live in HBM
    return (jax.device_put(g, jax.memory.Space.Host),)


_stream_leaf.defvjp(_fwd, _bwd)


def stream_to_device(tree: PyTree) -> PyTree:
    """Move every array leaf of a (host-resident) pytree into device memory;
    gradients flowing back through this are pinned to host. Traceable —
    intended for use INSIDE the compiled step (e.g. a scan body)."""
    return jax.tree.map(_stream_leaf, tree)


def place_on_host(tree: PyTree) -> PyTree:
    """Host-level helper: commit a pytree to pinned host memory (identity in
    spirit on backends without a separate host space, e.g. the CPU test
    backend, where the host space folds to device memory)."""
    return jax.device_put(tree, jax.memory.Space.Host)
