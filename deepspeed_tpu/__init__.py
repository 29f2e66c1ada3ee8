"""deepspeed_tpu — a TPU-native large-model training framework with the
capability surface of DeepSpeed v0.3.10, rebuilt on JAX/XLA/pjit/Pallas.

API façade mirrors reference deepspeed/__init__.py: ``initialize()`` returns
``(engine, optimizer, training_dataloader, lr_scheduler)``;
``add_config_arguments()`` injects the --deepspeed argparse group;
``init_distributed()`` boots the multi-host runtime (jax.distributed instead
of NCCL/torch.distributed).
"""

import time as _time

_import_started = _time.time()  # ``setup/import`` begins (its last line ends it)

from deepspeed_tpu import moe  # noqa: F401
from deepspeed_tpu import ops  # noqa: F401
from deepspeed_tpu.runtime.activation_checkpointing import checkpointing  # noqa: F401
from deepspeed_tpu.runtime.config import DeepSpeedConfig  # noqa: F401
from deepspeed_tpu.runtime.engine import DeepSpeedEngine
from deepspeed_tpu.runtime.lr_schedules import add_tuning_arguments
from deepspeed_tpu.utils.distributed import init_distributed  # noqa: F401
from deepspeed_tpu.utils.logging import log_dist, logger
from deepspeed_tpu.version import git_branch, git_hash, version as __version__

__git_hash__ = git_hash
__git_branch__ = git_branch

# Backwards compatibility with the old deepspeed.pt module structure
# (reference __init__.py:37-47).
import sys as _sys
import types as _types

from deepspeed_tpu.runtime import config as _rt_config, utils as _rt_utils
from deepspeed_tpu.runtime.fp16 import loss_scaler as _loss_scaler

pt = _types.ModuleType("pt", "dummy pt module for backwards compatability")
pt.deepspeed_utils = _rt_utils
pt.deepspeed_config = _rt_config
pt.loss_scaler = _loss_scaler
_sys.modules[__name__ + ".pt"] = pt
_sys.modules[__name__ + ".pt.deepspeed_utils"] = _rt_utils
_sys.modules[__name__ + ".pt.deepspeed_config"] = _rt_config
_sys.modules[__name__ + ".pt.loss_scaler"] = _loss_scaler


def initialize(args=None,
               model=None,
               optimizer=None,
               model_parameters=None,
               training_data=None,
               lr_scheduler=None,
               mpu=None,
               dist_init_required=None,
               collate_fn=None,
               config_params=None,
               mesh=None):
    """Initialize the DeepSpeed engine (reference deepspeed/__init__.py:50-139).

    Arguments keep the reference contract; ``model`` is a flax module (or any
    ``init``/``apply`` object), ``model_parameters`` the param pytree (or None
    for lazy init at first forward). A ``PipelineModule`` model selects the
    pipeline engine. Extra TPU-only kwarg: ``mesh`` to supply a prebuilt
    jax.sharding.Mesh.

    Returns: tuple of ``engine, optimizer, training_dataloader, lr_scheduler``.
    """
    log_dist("DeepSpeed info: version={}, git-hash={}, git-branch={}".format(
        __version__, git_hash, git_branch), ranks=[0])

    assert model is not None, "deepspeed.initialize requires a model"

    from deepspeed_tpu.pipe import PipelineModule
    if isinstance(model, PipelineModule):
        if getattr(model, "compiled", False):
            from deepspeed_tpu.runtime.pipe.compiled import (
                CompiledPipelineEngine as PipelineEngine)
        else:
            from deepspeed_tpu.runtime.pipe.engine import PipelineEngine
        engine = PipelineEngine(args=args,
                                model=model,
                                optimizer=optimizer,
                                model_parameters=model_parameters,
                                training_data=training_data,
                                lr_scheduler=lr_scheduler,
                                mpu=model.mpu() if hasattr(model, "mpu") else mpu,
                                dist_init_required=dist_init_required,
                                collate_fn=collate_fn,
                                config_params=config_params,
                                mesh=mesh)
    else:
        engine = DeepSpeedEngine(args=args,
                                 model=model,
                                 optimizer=optimizer,
                                 model_parameters=model_parameters,
                                 training_data=training_data,
                                 lr_scheduler=lr_scheduler,
                                 mpu=mpu,
                                 dist_init_required=dist_init_required,
                                 collate_fn=collate_fn,
                                 config_params=config_params,
                                 mesh=mesh)

    return_items = [
        engine,
        engine.optimizer,
        engine.training_dataloader,
        engine.lr_scheduler,
    ]
    return tuple(return_items)


def init_inference(model=None, params=None, config=None, mesh=None):
    """Initialize the serving engine (the reference's
    ``deepspeed.init_inference`` shape, which v0.3.10 does not have —
    its only inference surface is pipelined eval_batch).

    ``model`` is a GPT2LMHeadModel or a ``models.decoder.DecoderLM`` (or
    the config of either): the engine serves it through the adapter of its
    class (``inference.adapters.adapter_class_for``). ``params`` is the
    trained pytree. ``config`` may be an ``InferenceConfig``, a bare
    ``inference`` block dict, a full ds_config dict carrying an ``"inference"`` key, or
    a parsed ``DeepSpeedConfig``. Extra TPU-only kwarg: ``mesh`` — pass a
    mesh with a 'model' axis to serve a tensor-sharded model.

    Returns the ``InferenceEngine``.
    """
    from deepspeed_tpu.inference import InferenceConfig, InferenceEngine

    assert model is not None, "init_inference requires a model"
    assert params is not None, "init_inference requires trained params"
    if isinstance(config, DeepSpeedConfig):
        config = InferenceConfig.from_dict(config.inference)
    elif isinstance(config, dict) and "inference" in config:
        config = InferenceConfig.from_dict(config["inference"])
    return InferenceEngine(model, params, config=config, mesh=mesh)


def _add_core_arguments(parser):
    """Core DeepSpeed argparse group (reference __init__.py:142-190)."""
    group = parser.add_argument_group("DeepSpeed", "DeepSpeed configurations")
    group.add_argument("--deepspeed",
                       default=False,
                       action="store_true",
                       help="Enable DeepSpeed (helper flag for user code, no "
                       "impact on DeepSpeed backend)")
    group.add_argument("--deepspeed_config",
                       default=None,
                       type=str,
                       help="DeepSpeed json configuration file.")
    group.add_argument("--deepscale",
                       default=False,
                       action="store_true",
                       help="Deprecated enable DeepSpeed (helper flag for user "
                       "code, no impact on DeepSpeed backend)")
    group.add_argument("--deepscale_config",
                       default=None,
                       type=str,
                       help="Deprecated DeepSpeed json configuration file.")
    return parser


def add_config_arguments(parser):
    """Update an argument parser to enable ds_config parsing
    (reference __init__.py:193-206)."""
    parser = _add_core_arguments(parser)
    return parser


# The process's record of its own start-up (docs/OBSERVABILITY.md): the
# import above as ``setup/import``, and from here on every program JAX
# traces, lowers and compiles, by name.
from deepspeed_tpu.telemetry import (install_compile_listeners as _listen,
                                     process_recorder as _process_recorder)

_process_recorder().span("setup/import", _import_started)
_listen()
