"""benchmark/reference/gpt2.py, written from the published description,
agrees with the program's model at the tiny size in float32."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import gpt2 as reference
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel


@pytest.fixture(scope="module")
def tiny():
    cfg = GPT2Config.tiny(dtype=jnp.float32, use_flash_attention=False)
    model = GPT2LMHeadModel(cfg)
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, size=(3, 32)))
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    return cfg, model, params, ids


def test_logits_agree(tiny):
    cfg, model, params, ids = tiny
    want = model.apply({"params": params}, ids)
    got = reference.logits(params, ids, cfg.n_head)
    # float32 on both sides: only the order of summation differs
    assert float(jnp.max(jnp.abs(want - got))) < 1e-5


def test_loss_agrees(tiny):
    cfg, model, params, ids = tiny
    want = float(model.apply({"params": params}, ids, ids))
    got = float(reference.loss(params, ids, cfg.n_head))
    assert abs(want - got) < 1e-5
    assert abs(got - np.log(cfg.vocab_size)) < 0.2   # random weights


def test_reference_is_causal(tiny):
    cfg, _, params, ids = tiny
    changed = ids.at[:, -1].set((ids[:, -1] + 1) % cfg.vocab_size)
    a = reference.logits(params, ids, cfg.n_head)
    b = reference.logits(params, changed, cfg.n_head)
    assert float(jnp.max(jnp.abs(a[:, :-1] - b[:, :-1]))) == 0.0
    assert float(jnp.max(jnp.abs(a[:, -1] - b[:, -1]))) > 0.0
