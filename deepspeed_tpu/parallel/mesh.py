"""Device mesh + sharding construction — the TPU-native process-group layer.

Replaces the reference's torch.distributed/NCCL group machinery
(utils/distributed.py:11-41, runtime/pipe/topology.py:252-455): instead of
explicit process groups per axis, we build one ``jax.sharding.Mesh`` with named
axes ('pipe', 'data', 'model') mirroring ``PipeModelDataParallelTopology``
(topology.py:246-249), and express every collective as a sharding constraint or
``jax.lax`` collective over a named axis. XLA then lowers them onto ICI.

ZeRO sharding policy (SURVEY §7.1):
  stage 0 — params, grads, opt state replicated over 'data' (psum grads);
  stage 1 — opt state sharded over 'data';
  stage 2 — + grads reduce-scattered (psum_scatter) over 'data';
  stage 3 — + params sharded over 'data' (GSPMD gathers on use).
Sharding a pytree over 'data' picks, per leaf, the first axis divisible by the
axis size; indivisible leaves stay replicated (they are tiny: biases, norms).
"""

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"
PIPE_AXIS = "pipe"
SEQ_AXIS = "seq"


def build_mesh(num_dp: Optional[int] = None,
               num_mp: int = 1,
               num_pp: int = 1,
               num_sp: int = 1,
               devices=None) -> Mesh:
    """Build a ('pipe','data','seq','model') mesh over the given devices.

    Axis order puts 'model' innermost so tensor-parallel collectives ride the
    fastest ICI links, then 'seq' (ring-attention k/v rotations are the next
    hottest traffic), 'pipe' outermost (stage-adjacent transfers are light),
    matching the reference's default rank-mapping intent (topology.py:246-249).
    The 'seq' axis carries sequence (context) parallelism — beyond the
    reference, which has none in v0.3.10 (SURVEY §0).
    """
    explicit = devices is not None
    devices = devices if explicit else jax.devices()
    n = len(devices)
    if num_dp is None:
        assert n % (num_mp * num_pp * num_sp) == 0, \
            "{} devices not divisible by mp={} * pp={} * sp={}".format(
                n, num_mp, num_pp, num_sp)
        num_dp = n // (num_mp * num_pp * num_sp)
    assert num_dp * num_mp * num_pp * num_sp == n, \
        "mesh {}x{}x{}x{} != {} devices".format(num_pp, num_dp, num_sp,
                                                num_mp, n)
    shape = (num_pp, num_dp, num_sp, num_mp)
    dev_array = _arrange(devices, shape, explicit)
    return Mesh(dev_array, (PIPE_AXIS, DATA_AXIS, SEQ_AXIS, MODEL_AXIS))


def _arrange(devices, shape, explicit):
    """Physical device layout for the logical mesh shape.

    On real multi-chip TPU, a flat ``jax.devices()`` reshape gives the
    innermost ('model') axis no ICI-adjacency guarantee — tensor-parallel
    collectives would hop the torus arbitrarily. Delegate to
    ``jax.experimental.mesh_utils``, which maps logical axes onto the
    physical topology (innermost axes onto nearest-neighbor rings):

    - one ICI slice (single- or multi-host — a pod slice is one ICI
      domain regardless of process count): ``create_device_mesh``;
    - multiple slices (``slice_index`` differs, i.e. DCN between them):
      the scaling-book split — the data axis carries the cross-slice
      (DCN) factor, everything else ('pipe','seq','model' and the
      per-slice remainder of 'data') stays inside each slice's ICI
      domain via ``create_hybrid_device_mesh``.

    An EXPLICIT device list keeps the caller's order (tests and
    submesh-pinning callers depend on it), and non-TPU platforms keep the
    plain reshape (virtual CPU meshes have no topology; a reorder would
    only shuffle test determinism). A shape the topology solver refuses
    raises: a flat reshape there would run with an arbitrary ICI mapping
    and say nothing."""
    num_pp, num_dp, num_sp, num_mp = shape
    if explicit or not devices or devices[0].platform != "tpu" or \
            len(devices) == 1:
        return np.asarray(devices).reshape(shape)
    from jax.experimental import mesh_utils

    slices = len({getattr(d, "slice_index", 0) for d in devices})
    if slices > 1 and num_dp % slices == 0:
        return mesh_utils.create_hybrid_device_mesh(
            (num_pp, num_dp // slices, num_sp, num_mp),
            (1, slices, 1, 1), devices=devices)
    return mesh_utils.create_device_mesh(shape, devices=devices)


def default_mesh() -> Mesh:
    return build_mesh()


def replica_devices(n: int, devices=None):
    """Device per serving replica for a ServingFleet of ``n`` replicas
    (inference/fleet.py): round-robin over the visible devices, so n <=
    device_count gives each replica its own chip and n > device_count
    packs replicas fairly. On a single-device host (CPU tests) every
    replica shares the one device — the fleet then skips device_put
    entirely and replicas share the host params."""
    if n < 1:
        raise ValueError("replica count must be >= 1, got {}".format(n))
    devices = list(jax.devices()) if devices is None else list(devices)
    return [devices[i % len(devices)] for i in range(n)]


def dp_size(mesh: Mesh) -> int:
    return mesh.shape.get(DATA_AXIS, 1)


def mp_size(mesh: Mesh) -> int:
    return mesh.shape.get(MODEL_AXIS, 1)


def pp_size(mesh: Mesh) -> int:
    return mesh.shape.get(PIPE_AXIS, 1)


def sp_size(mesh: Mesh) -> int:
    return mesh.shape.get(SEQ_AXIS, 1)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Batch arrays: leading axis split over 'data'."""
    return NamedSharding(mesh, P(DATA_AXIS))


def _leaf_spec_over_axis(leaf, axis_name, axis_size):
    """PartitionSpec sharding the first evenly-divisible dim of ``leaf``."""
    shape = getattr(leaf, "shape", ())
    for dim, size in enumerate(shape):
        if size % axis_size == 0 and size >= axis_size:
            spec = [None] * len(shape)
            spec[dim] = axis_name
            return P(*spec)
    return P()


def tree_sharding_over_axis(mesh: Mesh, tree, axis_name=DATA_AXIS):
    """NamedSharding pytree: each leaf sharded along its first divisible dim."""
    size = mesh.shape.get(axis_name, 1)
    if size <= 1:
        rep = replicated(mesh)
        return jax.tree_util.tree_map(lambda _: rep, tree)
    return jax.tree_util.tree_map(
        lambda leaf: NamedSharding(mesh, _leaf_spec_over_axis(leaf, axis_name, size)),
        tree)


# Megatron-style tensor-parallel rules: (path regex, sharded dim). Column-
# parallel layers (qkv fusion, mlp up-projection) split their OUTPUT dim and
# bias; row-parallel layers (attn/mlp down-projection) split their INPUT dim
# with a replicated bias — XLA inserts the all-reduce the reference delegates
# to the user's Megatron mpu (SURVEY §0: TP is integrated, not implemented,
# engine.py:514-525; these rules make it implemented).
DEFAULT_TP_RULES = (
    # Expert parallelism FIRST (first match wins): stacked-expert params
    # (moe/layer.py Experts) carry a leading [num_experts] axis — shard it
    # over 'model' and the MoE dispatch/combine einsums become token
    # all-to-alls under GSPMD. Ordered before the Megatron rules because
    # an expert module may itself be an attn/mlp whose inner path would
    # otherwise match them and shard the wrong dim.
    (r".*experts/.*", 0),
    (r".*(attn/c_attn|mlp/c_fc)/kernel$", 1),
    (r".*(attn/c_attn|mlp/c_fc)/bias$", 0),
    (r".*(attn|mlp)/c_proj/kernel$", 0),
)


def _tp_dim(path_str, leaf, rules, mp):
    import re
    if mp <= 1 or rules is None:
        return None
    shape = getattr(leaf, "shape", ())
    for pattern, dim in rules:
        if re.match(pattern, path_str):
            # First PATTERN match decides; an indivisible dim means this
            # leaf is replicated, not handed to a later rule — falling
            # through would shard a semantically wrong dim (e.g. a
            # stacked expert with num_experts % mp != 0 landing on the
            # Megatron mlp rule and sharding its input dim).
            if dim < len(shape) and shape[dim] % mp == 0:
                return dim
            return None
    return None


def _path_str(path):
    return "/".join(str(getattr(p, "key", getattr(p, "name", p)))
                    for p in path)


def zero_shardings(mesh: Mesh, params, stage: int, tp_rules=None,
                   master_on_chips=True):
    """(param_sharding, grad_sharding, optstate_leaf_fn) for a ZeRO stage,
    composed with tensor parallelism when the mesh has a 'model' axis.

    Returns pytrees of NamedSharding for params and grads, plus a function
    mapping an opt-state leaf-template pytree to shardings (moments follow the
    param policy for their stage). A leaf matching a TP rule carries 'model'
    on its rule dim in EVERY role; the ZeRO 'data' axis lands on the first
    other divisible dim.

    ``params`` is the float32 MASTER, and from stage 1 on it lies as its
    moments lie (the reference's ZeRO-1/2 update "the local fp32 partition",
    stage1.py:246-265, stage2.py:1329-1491): the update is elementwise on
    local shards, and whoever computes with the weights gathers their
    compute-dtype cast. ``master_on_chips`` False (ZeRO-Offload: the master
    is on the host and what is placed is the compute copy) keeps a stage
    1-2 tree whole.
    """
    mp = mp_size(mesh)
    dp = dp_size(mesh)
    if tp_rules is None and mp > 1:
        tp_rules = DEFAULT_TP_RULES

    def leaf_spec(path, leaf, with_data):
        shape = getattr(leaf, "shape", ())
        spec = [None] * len(shape)
        tp = _tp_dim(_path_str(path), leaf, tp_rules, mp)
        if tp is not None:
            spec[tp] = MODEL_AXIS
        if with_data and dp > 1:
            for dim, size in enumerate(shape):
                if dim != tp and size % dp == 0 and size >= dp:
                    spec[dim] = DATA_AXIS
                    break
        return NamedSharding(mesh, P(*spec))

    def tree_spec(tree, with_data):
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: leaf_spec(path, leaf, with_data), tree)

    param_sh = tree_spec(params, stage >= (1 if master_on_chips else 3))
    grad_sh = tree_spec(params, stage >= 2)

    def opt_state_sharding(opt_state_template):
        return tree_spec(opt_state_template, stage >= 1)

    return param_sh, grad_sh, opt_state_sharding


def kv_cache_spec(mesh: Mesh, n_head: int, heads_dim: int = 2):
    """PartitionSpec for a slotted KV-cache plane [layers, slots, heads,
    max_len, head_dim]: heads over 'model' when divisible. Aligned with
    DEFAULT_TP_RULES' column-parallel qkv split — a tensor-sharded model's
    decode writes/reads only its local heads, and GSPMD inserts the same
    output-projection all-reduce as training. Indivisible head counts
    replicate (correct, just without the memory saving)."""
    mp = mp_size(mesh)
    if mp > 1 and n_head % mp == 0:
        spec = [None, None, None, None, None]
        spec[heads_dim] = MODEL_AXIS
        return P(*spec)
    return P()


def active_sp_axis(axis_name):
    """``axis_name`` IF the caller is being traced inside a shard_map that
    binds it; None otherwise. Lets a model switch to its sequence-parallel
    paths (ring attention, offset positions, psum'd losses) only when it
    actually runs token-sharded — init and serial eval stay untouched."""
    if axis_name is None:
        return None
    try:
        jax.lax.axis_index(axis_name)
    except NameError:
        return None
    return axis_name


def batch_partition_spec(x, dp, sp=1):
    """PartitionSpec for one batch array: leading axis over 'data' when
    divisible, second (token) axis over 'seq' when the mesh carries one.
    The single source of the batch-sharding heuristic — used by
    shard_batch's device_put AND the engine's shard_map in_specs (sparse
    grads, sequence parallelism)."""
    shape = getattr(x, "shape", ())
    if len(shape) == 0 or shape[0] % dp != 0:
        return P()
    if sp > 1 and len(shape) > 1 and shape[1] % sp == 0:
        return P(DATA_AXIS, SEQ_AXIS)
    return P(DATA_AXIS)


def shard_batch(mesh: Mesh, batch):
    """device_put a host batch: leading axis split over 'data', and the
    second (sequence) axis over 'seq' when the mesh carries one."""
    if dp_size(mesh) <= 1 and mp_size(mesh) <= 1 and pp_size(mesh) <= 1 \
            and sp_size(mesh) <= 1:
        return batch
    dp, sp = dp_size(mesh), sp_size(mesh)

    def _put(x):
        return jax.device_put(
            x, NamedSharding(mesh, batch_partition_spec(x, dp, sp)))

    return jax.tree_util.tree_map(_put, batch)
