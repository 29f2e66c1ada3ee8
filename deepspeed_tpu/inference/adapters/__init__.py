"""Model adapters — the engine<->model protocol and its implementations.

See protocol.py for the contract and docs/ADAPTERS.md for how to bring
a new model. The graftlint ADAPTER rule keeps ``models.generation``
imports inside ``inference/`` confined to ``adapters/gpt2.py``.
"""

from deepspeed_tpu.inference.adapters.protocol import ModelAdapter
from deepspeed_tpu.inference.adapters.gpt2 import GPT2Adapter
from deepspeed_tpu.inference.adapters.longcontext import LongContextAdapter
from deepspeed_tpu.inference.adapters.decoder import DecoderAdapter
from deepspeed_tpu.models.decoder import DecoderConfig, DecoderLM


def adapter_class_for(model):
    """The adapter that serves ``model``, told by what can be observed: the
    model's class. A ``models.decoder.DecoderLM`` (or its config) is the
    config-driven decoder block; anything else is taken for GPT-2, as
    ``init_inference`` always has (a GPT2LMHeadModel, its config, a
    ``_GenCfg``)."""
    if isinstance(model, (DecoderLM, DecoderConfig)):
        return DecoderAdapter
    return GPT2Adapter


__all__ = ["adapter_class_for", "ModelAdapter", "GPT2Adapter",
           "LongContextAdapter", "DecoderAdapter"]
