"""Collective correctness on the virtual CPU mesh (reference analogue:
tests/unit/comm/test_dist.py, run here without multi-process forking)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deepspeed_tpu import comm
from deepspeed_tpu.comm.mesh import MeshConfig, build_mesh
from jax import shard_map


def test_mesh_shapes():
    mesh = build_mesh(MeshConfig(data=2, fsdp=2, model=2))
    assert mesh.shape["data"] == 2
    assert mesh.shape["model"] == 2
    assert comm.data_parallel_size(mesh) == 4


def test_mesh_remainder_axis():
    mesh = build_mesh(MeshConfig(data=-1, model=2))
    assert mesh.shape["data"] == 4


def test_mesh_invalid():
    with pytest.raises(ValueError):
        build_mesh(MeshConfig(data=3, model=3))


def _shmap(mesh, f, in_spec, out_spec):
    return shard_map(f, mesh=mesh, in_specs=in_spec, out_specs=out_spec, check_vma=False)


def test_all_reduce(mesh8):
    x = jnp.arange(8.0)

    def f(xs):
        return comm.all_reduce(xs, "data")

    out = _shmap(mesh8, f, P("data"), P("data"))(x)
    np.testing.assert_allclose(out, np.full(8, 28.0))


def test_reduce_scatter(mesh8):
    x = jnp.ones((8, 8))

    def f(xs):  # xs [1, 8] per device -> scatter over rows
        return comm.reduce_scatter(xs.sum(0), "data")

    out = _shmap(mesh8, f, P("data", None), P("data"))(x)
    np.testing.assert_allclose(out, np.full(8, 8.0))


def test_all_gather(mesh8):
    x = jnp.arange(8.0)

    def f(xs):
        return comm.all_gather(xs, "data")

    out = _shmap(mesh8, f, P("data"), P(None))(x)
    np.testing.assert_allclose(out, np.arange(8.0))


def test_all_to_all(mesh8):
    x = jnp.arange(64.0).reshape(8, 8)

    def f(xs):  # [1, 8] per device: row i of x
        return comm.all_to_all(xs, "data", split_axis=1, concat_axis=0)

    # device j ends up with column j of x as an [8, 1] block; assembling those
    # blocks along axis 1 reconstructs x — i.e. all_to_all re-distributes the
    # sharded dim from rows to columns without changing values.
    out = _shmap(mesh8, f, P("data", None), P(None, "data"))(x)
    np.testing.assert_allclose(out, np.arange(64.0).reshape(8, 8))


def test_ring_shift(mesh8):
    x = jnp.arange(8.0)

    def f(xs):
        return comm.ring_shift(xs, "data", shift=1)

    out = _shmap(mesh8, f, P("data"), P("data"))(x)
    np.testing.assert_allclose(out, np.roll(np.arange(8.0), 1))


def test_broadcast_in_axis(mesh8):
    x = jnp.arange(8.0)

    def f(xs):
        return comm.broadcast_in_axis(xs, "data", src_index=3)

    out = _shmap(mesh8, f, P("data"), P("data"))(x)
    np.testing.assert_allclose(out, np.full(8, 3.0))


def test_bw_calc():
    alg, bus = comm.get_bw("all_reduce", 1e9, 0.1, 8)
    assert alg == pytest.approx(10.0)
    assert bus == pytest.approx(10.0 * 2 * 7 / 8)


@pytest.mark.slow  # ~12s warm; the 1-bit error-feedback path is covered
# warm end-to-end by test_onebit (adam/lamb convergence-parity + packed-wire
# tests) — this is the isolated-collective variant of the same contract
def test_compressed_allreduce_error_feedback(mesh8):
    """1-bit error-feedback allreduce (reference runtime/comm/nccl.py:51):
    per-iteration output is the sign-compressed average; accumulated over K
    iterations the error feedback makes it unbiased:
    sum_k avg_k + mean(err_K) == K * mean(t)."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepspeed_tpu.comm import compressed_allreduce

    world = 8
    rng = np.random.default_rng(0)
    t_host = rng.standard_normal((world, 16, 4)).astype(np.float32)
    sh = NamedSharding(mesh8, P("data"))
    t = jax.device_put(jnp.asarray(t_host), sh)
    err = jax.device_put(jnp.zeros_like(t), sh)

    true_mean = t_host.mean(axis=0)
    acc = np.zeros_like(true_mean)
    K = 5
    for _ in range(K):
        avg, err = compressed_allreduce(t, err, axis="data", mesh=mesh8)
        acc += np.asarray(avg)
    resid = np.asarray(err).mean(axis=0)
    np.testing.assert_allclose(acc + resid, K * true_mean, rtol=1e-4, atol=1e-4)


def test_compressed_backend_object_api(mesh8):
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepspeed_tpu.comm import CompressedBackend

    sh = NamedSharding(mesh8, P("data"))
    t = jax.device_put(jnp.ones((8, 4)), sh)
    err = jax.device_put(jnp.zeros((8, 4)), sh)
    be = CompressedBackend(axis="data", mesh=mesh8)
    avg, err2 = be.compressed_allreduce(t, err)
    np.testing.assert_allclose(np.asarray(avg), np.ones((4,)), rtol=1e-5)


def test_mpi_discovery_multinode_requires_master_addr(monkeypatch):
    from deepspeed_tpu.comm.collectives import mpi_discovery

    monkeypatch.setenv("OMPI_COMM_WORLD_RANK", "1")
    monkeypatch.setenv("OMPI_COMM_WORLD_SIZE", "2")
    monkeypatch.delenv("MASTER_ADDR", raising=False)
    with pytest.raises(RuntimeError):
        mpi_discovery()
    monkeypatch.setenv("MASTER_ADDR", "10.0.0.1")
    assert mpi_discovery() == {"rank": 1, "world_size": 2,
                               "coordinator": "10.0.0.1:29500"}


def test_hybrid_mesh_falls_back_single_slice():
    """build_hybrid_mesh on a single-slice (CPU) topology = plain build_mesh;
    multi-slice ordering needs hardware with slice_index and is exercised by
    the driver's multichip dryrun + real pods."""
    from deepspeed_tpu.comm.mesh import MeshConfig, build_hybrid_mesh

    mesh = build_hybrid_mesh(MeshConfig(data=2, fsdp=2, model=2))
    assert dict(mesh.shape) == {"pipe": 1, "data": 2, "fsdp": 2,
                                "context": 1, "model": 2}


def test_hybrid_mesh_multislice_device_order():
    """Simulated 2-slice topology: the dcn axis (data) must change across
    slices — every (fsdp, model, ...) column stays within one slice."""
    import types

    from deepspeed_tpu.comm.mesh import MeshConfig, build_hybrid_mesh

    real = jax.devices()

    class FakeDev:
        def __init__(self, d, idx, slice_index):
            self._d = d
            self.id = idx
            self.slice_index = slice_index
            self.process_index = slice_index
            self.platform = d.platform
            self.device_kind = d.device_kind

        def __repr__(self):
            return f"fake(id={self.id}, slice={self.slice_index})"

    fakes = [FakeDev(real[i], i, i // 4) for i in range(8)]
    mesh = build_hybrid_mesh(MeshConfig(data=2, fsdp=2, model=2), devices=fakes)
    arr = np.asarray(mesh.devices.tolist())
    # data is axis 'data' (index 1 of AXIS_ORDER): slices must differ across it
    data_axis = list(mesh.axis_names).index("data")
    moved = np.moveaxis(np.vectorize(lambda d: d.slice_index)(mesh.devices), data_axis, 0)
    assert (moved[0] != moved[1]).all() or (moved[0] == 0).all() and (moved[1] == 1).all()
    # and within a data index, the slice is constant
    assert len(set(moved[0].ravel().tolist())) == 1
    assert len(set(moved[1].ravel().tolist())) == 1


def test_mpi_discovery_single_node_local_size(monkeypatch):
    """All ranks on one host (LOCAL_SIZE == SIZE): hostname fallback is safe
    and must not raise even without MASTER_ADDR."""
    from deepspeed_tpu.comm.collectives import mpi_discovery

    monkeypatch.setenv("OMPI_COMM_WORLD_RANK", "1")
    monkeypatch.setenv("OMPI_COMM_WORLD_SIZE", "4")
    monkeypatch.setenv("OMPI_COMM_WORLD_LOCAL_SIZE", "4")
    monkeypatch.delenv("MASTER_ADDR", raising=False)
    d = mpi_discovery()
    assert d["world_size"] == 4 and ":" in d["coordinator"]


def test_hybrid_mesh_factors_dcn_axis_over_slices():
    """data=8 over 2 slices: dcn component 2, within-slice remainder 4."""
    from deepspeed_tpu.comm.mesh import MeshConfig, build_hybrid_mesh

    real = jax.devices()

    class FakeDev:
        def __init__(self, d, idx, slice_index):
            self.id = idx
            self.slice_index = slice_index
            self.process_index = slice_index
            self.platform = d.platform
            self.device_kind = d.device_kind

        def __repr__(self):
            return f"fake(id={self.id}, slice={self.slice_index})"

    fakes = [FakeDev(real[i], i, i // 4) for i in range(8)]
    mesh = build_hybrid_mesh(MeshConfig(data=8), devices=fakes)
    assert dict(mesh.shape)["data"] == 8
    # each half of the data axis lives in one slice
    slices = np.vectorize(lambda d: d.slice_index)(mesh.devices).ravel()
    assert sorted(set(slices.tolist())) == [0, 1]
