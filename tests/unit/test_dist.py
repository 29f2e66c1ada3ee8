"""Distributed-bootstrap tests (mirror reference tests/unit/test_dist.py,
which exercises init + an allreduce on forked ranks): env-contract parsing,
MPI discovery, and a real psum over the 8-device mesh stand in for the NCCL
world."""

import os

import jax
import jax.experimental.mesh_utils  # noqa: F401 (registers the attr the monkeypatch below replaces)
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from deepspeed_tpu.utils import distributed as dist


def test_single_process_init_is_noop(monkeypatch):
    monkeypatch.setattr(dist, "_initialized", False)
    monkeypatch.delenv("WORLD_SIZE", raising=False)
    dist.init_distributed()
    assert dist.is_initialized()


def test_mpi_discovery_sets_env(monkeypatch):
    monkeypatch.setattr(dist, "_initialized", False)
    for k in ("RANK", "WORLD_SIZE", "LOCAL_RANK", "MASTER_ADDR",
              "MASTER_PORT"):
        monkeypatch.delenv(k, raising=False)
        # mpi_discovery writes os.environ directly; register each key with
        # monkeypatch so the writes are rolled back after the test (a
        # leaked WORLD_SIZE=4 would make a later init_distributed try a
        # real 4-process rendezvous).
        monkeypatch.setenv(k, "sentinel")
        monkeypatch.delenv(k)
    monkeypatch.setenv("OMPI_COMM_WORLD_RANK", "3")
    monkeypatch.setenv("OMPI_COMM_WORLD_SIZE", "4")
    monkeypatch.setenv("OMPI_COMM_WORLD_LOCAL_RANK", "1")
    dist.mpi_discovery(distributed_port=12345)
    assert os.environ["RANK"] == "3"
    assert os.environ["WORLD_SIZE"] == "4"
    assert os.environ["LOCAL_RANK"] == "1"
    assert os.environ["MASTER_PORT"] == "12345"


def test_init_already_initialized_is_idempotent(monkeypatch):
    monkeypatch.setattr(dist, "_initialized", True)
    dist.init_distributed()  # must not raise or re-init
    assert dist.is_initialized()


def test_allreduce_over_mesh(eight_devices):
    """The reference's test_dist does dist.all_reduce across ranks; the
    TPU-native equivalent is a psum over the mesh axis."""
    mesh = Mesh(np.asarray(eight_devices), ("data",))

    def body(x):
        return jnp.broadcast_to(jax.lax.psum(x.sum(), "data"), (1,))

    out = shard_map(body, mesh=mesh, in_specs=P("data"),
                    out_specs=P("data"))(jnp.arange(8.0))
    np.testing.assert_allclose(np.asarray(out), np.full((8,), 28.0))


def test_build_mesh_four_axes(eight_devices):
    """('pipe','data','seq','model') mesh construction + size helpers."""
    import jax

    from deepspeed_tpu.parallel import mesh as mesh_lib

    m = mesh_lib.build_mesh(devices=jax.devices()[:8], num_sp=4, num_dp=2)
    assert dict(m.shape) == {"pipe": 1, "data": 2, "seq": 4, "model": 1}
    assert mesh_lib.dp_size(m) == 2
    assert mesh_lib.sp_size(m) == 4
    assert mesh_lib.mp_size(m) == 1
    assert mesh_lib.pp_size(m) == 1


def test_batch_partition_spec_policy():
    """The single batch-sharding heuristic: batch dim over 'data' when
    divisible, token dim over 'seq' when present and divisible."""
    import numpy as np

    from deepspeed_tpu.parallel.mesh import batch_partition_spec as spec
    from jax.sharding import PartitionSpec as P

    x2 = np.zeros((8, 32))
    x1 = np.zeros((8,))
    assert spec(x2, dp=2, sp=4) == P("data", "seq")
    assert spec(x2, dp=2) == P("data")
    assert spec(x1, dp=2, sp=4) == P("data")
    assert spec(np.zeros((7, 32)), dp=2, sp=4) == P()   # indivisible batch
    assert spec(np.zeros((8, 33)), dp=2, sp=4) == P("data")  # token dim odd
    assert spec(np.float32(1.0), dp=2, sp=4) == P()     # scalar


def test_active_sp_axis_outside_shard_map():
    from deepspeed_tpu.parallel.mesh import active_sp_axis

    assert active_sp_axis(None) is None
    assert active_sp_axis("seq") is None  # not bound outside shard_map


def test_arrange_topology_paths(monkeypatch):
    """_arrange: explicit lists and CPU devices keep caller/flat order;
    fake-TPU devices route through mesh_utils (hybrid when multi-process,
    ICI-aware otherwise). A solver failure raises:
    tests/unit/test_chip_smoke.py::test_arrange_reraises."""
    import jax.experimental

    from deepspeed_tpu.parallel import mesh as mesh_lib

    class FakeDev:
        platform = "tpu"

        def __init__(self, i, slice_index=0):
            self.id = i
            self.slice_index = slice_index

        def __repr__(self):
            return "d{}".format(self.id)

    cpus = jax.devices()[:8]
    shape = (1, 2, 1, 4)

    # Explicit list => caller order, even for "tpu" devices.
    tpus = [FakeDev(i) for i in range(8)]
    arr = mesh_lib._arrange(tpus, shape, explicit=True)
    assert [d.id for d in arr.reshape(-1)] == list(range(8))
    # CPU platform => flat order.
    arr = mesh_lib._arrange(cpus, shape, explicit=False)
    assert list(arr.reshape(-1)) == list(cpus)

    calls = {}

    class FakeMeshUtils:
        @staticmethod
        def create_device_mesh(shape_, devices=None):
            calls["single"] = shape_
            return np.asarray(devices).reshape(shape_)

        @staticmethod
        def create_hybrid_device_mesh(ici, dcn, devices=None):
            calls["hybrid"] = (ici, dcn)
            return np.asarray(devices).reshape(
                tuple(i * d for i, d in zip(ici, dcn)))

    monkeypatch.setattr(jax.experimental, "mesh_utils", FakeMeshUtils)

    arr = mesh_lib._arrange(tpus, shape, explicit=False)
    assert calls["single"] == shape and arr.shape == shape

    # One ICI slice spanning multiple hosts must STILL take the
    # single-slice path (a pod slice is one ICI domain); only genuinely
    # multi-slice (DCN-connected) device sets go hybrid.
    two_slice = [FakeDev(i, slice_index=i // 4) for i in range(8)]
    calls.clear()
    arr = mesh_lib._arrange(two_slice, shape, explicit=False)
    # dp=2 splits across 2 slices: dcn carries data, ICI the rest.
    assert calls == {"hybrid": ((1, 1, 1, 4), (1, 2, 1, 1))}
    assert arr.shape == shape
