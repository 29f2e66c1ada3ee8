"""Import smoke for the graft modules the serving and MoE stacks depend on.

The training-side MoE layer routes through ``moe/sharded_moe.py`` and
LongContextAdapter builds its masks from
``ops/sparse_attention/sparsity_config.py`` — if either tree stops
importing under the pinned jax, every test downstream fails with a
confusing collection error. Pin the imports directly (and the few public
symbols their callers actually touch) so a toolchain bump that breaks
them fails HERE with the module name in the assertion, not three layers
up.
"""

import importlib

import pytest

MODULES = (
    "deepspeed_tpu.moe",
    "deepspeed_tpu.moe.layer",
    "deepspeed_tpu.moe.sharded_moe",
    "deepspeed_tpu.moe.utils",
    "deepspeed_tpu.ops.sparse_attention",
    "deepspeed_tpu.ops.sparse_attention.bert_sparse_self_attention",
    "deepspeed_tpu.ops.sparse_attention.kernels",
    "deepspeed_tpu.ops.sparse_attention.sparse_attention_utils",
    "deepspeed_tpu.ops.sparse_attention.sparse_self_attention",
    "deepspeed_tpu.ops.sparse_attention.sparsity_config",
)


@pytest.mark.parametrize("name", MODULES)
def test_module_imports(name):
    mod = importlib.import_module(name)
    assert mod.__name__ == name


def test_sharded_moe_surface():
    from deepspeed_tpu.moe import sharded_moe
    # The routing entry point the MoE layer drives.
    assert callable(sharded_moe.top1gating)


def test_sparsity_config_surface():
    from deepspeed_tpu.ops.sparse_attention import sparsity_config
    # The layout builder LongContextAdapter's masks come from.
    cfg = sparsity_config.FixedSparsityConfig(num_heads=1, block=8)
    layout = cfg.make_layout(64)
    assert tuple(layout.shape) == (1, 8, 8)
