"""Runtime-utils tests (mirror reference tests/unit/test_runtime_utils.py +
test_partition.py): balanced/uniform layer partitioners, prefix sums, and
PartitionedTensor shard/meta/rebuild round-trips — host-side and via a real
all_gather over the 8-device mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from deepspeed_tpu.runtime.utils import (
    PartitionedTensor,
    partition_balanced,
    partition_uniform,
    prefix_sum_inc,
)


def assert_valid_partition(weights, parts, num_parts):
    n = len(weights)
    assert len(parts) == num_parts + 1
    assert parts[0] == 0
    assert parts[num_parts] == n
    for idx in range(num_parts):
        assert parts[idx] <= parts[idx + 1]


def partition_weights(weights, parts):
    return [sum(weights[parts[p]:parts[p + 1]])
            for p in range(len(parts) - 1)]


def test_prefix_sum():
    assert prefix_sum_inc([3, 4, 5]) == [3, 7, 12]


@pytest.mark.parametrize("fn", [partition_uniform, partition_balanced])
def test_valid_and_short_partitions(fn):
    for n, p in [(10, 1), (2, 4), (8, 4), (1, 1)]:
        weights = [1] * n
        parts = fn(len(weights), p) if fn is partition_uniform \
            else fn(weights, p)
        assert_valid_partition(weights, parts, p)


def test_easy_balance():
    weights = [1] * 8
    for parts in (partition_uniform(8, 4), partition_balanced(weights, 4)):
        assert_valid_partition(weights, parts, 4)
        assert all(c == 2 for c in partition_weights(weights, parts))


def test_hard_balance_balanced_beats_uniform():
    """partition_balanced must equalize weighted cost where uniform can't
    (reference test_partition.py hard-balance cases)."""
    weights = [10, 1, 1, 1, 1, 1, 1, 10]
    parts = partition_balanced(weights, 4)
    assert_valid_partition(weights, parts, 4)
    costs = partition_weights(weights, parts)
    assert max(costs) <= 12  # uniform would put 13 in an end bin


def test_partitioned_tensor_roundtrip_host():
    rng = np.random.RandomState(0)
    full = jnp.asarray(rng.randn(4 * 4, 3).astype(np.float32))
    parts = [PartitionedTensor(full, group_size=4, rank=r) for r in range(4)]
    for part in parts:
        assert np.isscalar(part.local_size()) or part.local_size() > 0
        assert part.local_size() * 4 >= full.size
    rebuilt = jnp.concatenate([p.data() for p in parts]).reshape(-1)
    np.testing.assert_array_equal(
        np.asarray(rebuilt[:full.size].reshape(full.shape)),
        np.asarray(full))


def test_partitioned_tensor_meta_roundtrip():
    rng = np.random.RandomState(1)
    full = jnp.asarray(rng.randn(4 * 7, 3).astype(np.float32))
    part = PartitionedTensor(full, group_size=4, rank=2)
    meta = part.to_meta()
    again = PartitionedTensor.from_meta(meta, part.local_data,
                                        group_size=4, rank=2)
    assert again.orig_size == tuple(full.shape)
    np.testing.assert_array_equal(np.asarray(again.data()),
                                  np.asarray(part.data()))


def test_partitioned_tensor_full_all_gather(eight_devices):
    """full() inside shard_map rebuilds the tensor with a REAL all_gather
    over the mesh axis (reference test_partition.py:test_partitioned_tensor
    does the NCCL equivalent on 4 ranks)."""
    world = 8
    rng = np.random.RandomState(2)
    full = rng.randn(world * 4, 3).astype(np.float32)
    mesh = Mesh(np.asarray(eight_devices), ("data",))

    def body(x):
        part = PartitionedTensor(jnp.asarray(full), group_size=world,
                                 rank=jax.lax.axis_index("data"))
        return part.full(axis_name="data")[None]

    out = shard_map(body, mesh=mesh, in_specs=P("data"),
                    out_specs=P("data"), check_vma=False)(
        jnp.zeros((world, 1), jnp.float32))
    for r in range(world):
        np.testing.assert_allclose(np.asarray(out[r]), full, rtol=1e-6)
