"""Named-axis collectives — the ``deepspeed.comm`` façade, TPU-native.

The reference exposes a torch.distributed-shaped API (``comm/comm.py:14-22``
states the compatibility contract) whose ops execute eagerly over NCCL. Under
XLA, collectives are *compiled*: these wrappers are meant to be called inside
``jit``/``shard_map``-traced code with a mesh axis name where the reference
took a process group. Logging therefore happens at trace time (op + axis +
bytes), and measured latencies come from the profiler, not per-op timers
(SURVEY.md §5 "per-collective logging must be re-implemented at trace time").

Mapping (reference op → here):
    all_reduce          → all_reduce (lax.psum / pmean)        comm/comm.py:494
    reduce_scatter_base → reduce_scatter (lax.psum_scatter)    comm/comm.py:256
    all_gather_base     → all_gather (lax.all_gather)          comm/comm.py:325
    all_to_all_single   → all_to_all (lax.all_to_all)          comm/comm.py:222
    send/recv (PP p2p)  → ppermute shifts                      pipe/p2p.py:48
    broadcast           → implicit: replicated shardings; or pbroadcast
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..utils.logging import logger
from .logger import comms_logger

_INITIALIZED = False


def init_distributed(
    dist_backend: str = "xla",
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    auto_mpi_discovery: bool = True,
    **_: object,
) -> None:
    """Multi-host bootstrap — replaces ``deepspeed.init_distributed``
    (comm/comm.py:577). Rendezvous goes through ``jax.distributed.initialize``
    instead of MASTER_ADDR + init_process_group. Single-process (or an
    externally initialized jax.distributed) is a no-op.
    """
    global _INITIALIZED
    if _INITIALIZED:
        return
    if coordinator_address is None:
        coordinator_address = os.environ.get("DSTPU_COORDINATOR")
    if num_processes is None and os.environ.get("DSTPU_NUM_PROCESSES"):
        num_processes = int(os.environ["DSTPU_NUM_PROCESSES"])
    if process_id is None and os.environ.get("DSTPU_PROCESS_ID"):
        process_id = int(os.environ["DSTPU_PROCESS_ID"])
    if coordinator_address is None and auto_mpi_discovery:
        disc = mpi_discovery()
        if disc is not None:
            coordinator_address = disc["coordinator"]
            num_processes = num_processes or disc["world_size"]
            process_id = process_id if process_id is not None else disc["rank"]
            logger.info(f"rendezvous discovered from MPI/scheduler env: {disc}")
    # num_processes=None lets jax.distributed auto-detect (TPU pod metadata);
    # only an explicit single-process launch skips rendezvous.
    if coordinator_address is not None and num_processes != 1:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
        logger.info(
            f"jax.distributed initialized: process {jax.process_index()}/{jax.process_count()}"
        )
    _INITIALIZED = True


def mpi_discovery(port: int = 29500) -> Optional[dict]:
    """Derive (rank, world_size, coordinator) from a launcher's environment —
    the reference's ``mpi_discovery`` + AML/SageMaker paths (comm/comm.py:640-
    750), minus any actual MPI import: the variables the launchers export are
    enough, and the transport is jax.distributed either way.

    Recognized: OpenMPI (OMPI_*), MVAPICH/PMI (MV2_*/PMI_*), torchrun-style
    (RANK/WORLD_SIZE + MASTER_ADDR), Azure-ML (AZ_BATCH_MASTER_NODE).
    Returns None when nothing is set."""
    env = os.environ
    rank = size = None
    for rk, sk in (("OMPI_COMM_WORLD_RANK", "OMPI_COMM_WORLD_SIZE"),
                   ("MV2_COMM_WORLD_RANK", "MV2_COMM_WORLD_SIZE"),
                   ("PMI_RANK", "PMI_SIZE"),
                   ("RANK", "WORLD_SIZE")):
        if rk in env and sk in env:
            rank, size = int(env[rk]), int(env[sk])
            break
    if rank is None:
        return None
    if "AZ_BATCH_MASTER_NODE" in env:  # AML: "<ip>:<port>"
        coordinator = env["AZ_BATCH_MASTER_NODE"]
    elif "MASTER_ADDR" in env:
        coordinator = f"{env['MASTER_ADDR']}:{env.get('MASTER_PORT', port)}"
    else:
        local = None
        for lk in ("OMPI_COMM_WORLD_LOCAL_SIZE", "MPI_LOCALNRANKS", "MV2_COMM_WORLD_LOCAL_SIZE"):
            if lk in env:
                local = int(env[lk])
                break
        if size > 1 and local != size:
            # multi-host (or unknown): guessing each rank's own hostname
            # would point every node's rendezvous at itself and hang
            # jax.distributed.initialize
            raise RuntimeError(
                "mpi_discovery: MPI rank env found but no MASTER_ADDR / "
                "AZ_BATCH_MASTER_NODE — export MASTER_ADDR=<rank-0 host> "
                "(mpirun -x MASTER_ADDR=...) for multi-node runs")
        # single process, or all ranks on this host: every rank resolves the
        # same machine
        import socket

        coordinator = f"{socket.gethostname()}:{port}"
    return {"rank": rank, "world_size": size, "coordinator": coordinator}


def is_initialized() -> bool:
    return _INITIALIZED


def get_world_size(group: Optional[str] = None) -> int:
    """Device count; with ``group`` = a mesh axis name (the TPU analogue of a
    process group), the size of that axis on the most recently built mesh."""
    if group is not None:
        from .mesh import current_mesh

        mesh = current_mesh()
        if mesh is None or group not in mesh.shape:
            raise ValueError(
                f"unknown group {group!r}: no active mesh axis by that name "
                f"(have {list(mesh.shape) if mesh else 'no mesh'})"
            )
        return int(mesh.shape[group])
    return len(jax.devices())


def get_rank() -> int:
    return jax.process_index()


def get_local_rank() -> int:
    """Process index within its host. One JAX process drives all of a host's
    chips, so this is the LOCAL_RANK the launcher exported (launcher/launch.py)
    — 0 unless a per-chip launch scheme set it."""
    return int(os.environ.get("LOCAL_RANK", 0))


def barrier() -> None:
    """True cross-process rendezvous (reference comm barrier): every process
    must enter before any returns. No-op single-process."""
    if jax.process_count() == 1:
        return
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices("deepspeed_tpu.barrier")


# --------------------------------------------------------------------------
# In-jit collectives over named mesh axes.
# --------------------------------------------------------------------------

def _log(op: str, axis, tensor) -> None:
    comms_logger.record(op, axis, tensor)


def all_reduce(x, axis, op: str = "sum"):
    """lax.psum/pmax/... over a mesh axis (reference comm/comm.py:494)."""
    _log(f"all_reduce[{op}]", axis, x)
    if op == "sum":
        return lax.psum(x, axis)
    if op in ("mean", "avg"):
        return lax.pmean(x, axis)
    if op == "max":
        return lax.pmax(x, axis)
    if op == "min":
        return lax.pmin(x, axis)
    raise ValueError(f"unsupported reduce op {op}")


def reduce_scatter(x, axis, scatter_dimension: int = 0, tiled: bool = True):
    """lax.psum_scatter — the ZeRO-2 gradient primitive (comm/comm.py:256)."""
    _log("reduce_scatter", axis, x)
    return lax.psum_scatter(x, axis, scatter_dimension=scatter_dimension, tiled=tiled)


def all_gather(x, axis, gather_dimension: int = 0, tiled: bool = True):
    """lax.all_gather — the ZeRO-3 param-fetch primitive (comm/comm.py:325)."""
    _log("all_gather", axis, x)
    return lax.all_gather(x, axis, axis=gather_dimension, tiled=tiled)


def all_to_all(x, axis, split_axis: int, concat_axis: int, tiled: bool = True):
    """lax.all_to_all — MoE dispatch (reference moe/sharded_moe.py:89 _AllToAll)."""
    _log("all_to_all", axis, x)
    return lax.all_to_all(x, axis, split_axis=split_axis, concat_axis=concat_axis, tiled=tiled)


def ppermute(x, axis, perm):
    """Point-to-point permutation — PP sends and ring patterns (pipe/p2p.py:48)."""
    _log("ppermute", axis, x)
    return lax.ppermute(x, axis, perm)


def ring_shift(x, axis, shift: int = 1):
    """Shift values around the ring formed by a mesh axis (ring attention, PP)."""
    n = lax.axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return ppermute(x, axis, perm)


def broadcast_in_axis(x, axis, src_index: int = 0):
    """Select src rank's value on all ranks of the axis (comm/comm.py:222 broadcast)."""
    _log("broadcast", axis, x)
    gathered = lax.all_gather(x, axis)
    return jax.tree.map(lambda g: g[src_index], gathered)


def axis_index(axis):
    return lax.axis_index(axis)


def axis_size_in_jit(axis):
    return lax.axis_size(axis)
