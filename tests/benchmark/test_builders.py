"""What a driver and the readers ask of a ``model_builders/<model_type>.py``
(benchmark/README.md, "What a builder owes"), held against every builder
found under ``paths`` at the size of a stand-in that runs it."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from tests.benchmark import tiny

# Every kind of traffic asks for these; ``train`` and ``serve`` add theirs.
ATTRIBUTES = {"module": object, "vocab_size": int, "n_layer": int,
              "n_head": int, "head_dim": int}
METHODS = {"any": ("sizes", "init_params"),
           "serve": ("reference_logits", "kv_bytes_per_token_layer"),
           "train": ("reference_loss", "train_flops_per_token")}


def _builders():
    return sorted({os.path.basename(f)[:-len(".py")]
                   for f in harness.find_all("model_builders", ".py")})


def _stand_ins_of(model_type):
    """(configuration, kind of traffic) of every stand-in that runs the
    builder ``model_type``."""
    paths = harness.paths()
    found = []
    for standin in tiny.standins().values():
        config = harness.load_json(harness._find(
            paths, "configs", standin["config"] + ".json"))
        if config["model_type"] == model_type:
            found.append((config, harness.load_json(harness._find(
                paths, "workloads", standin["traffic"] + ".json"))["kind"]))
    return found


@pytest.mark.parametrize("model_type", _builders())
def test_a_builder_gives_what_the_drivers_and_readers_ask(model_type):
    ran_by = _stand_ins_of(model_type)
    if not ran_by:
        # test_manifest.py names the stand-in a cell lacks; a builder that
        # no cell uses owes nothing
        pytest.skip("no stand-in runs model_builders/{}.py".format(
            model_type))
    config = ran_by[0][0]
    kinds = {kind for _, kind in ran_by}
    model = harness.load_by_name("model_builders", model_type).Model(config)

    for name, kind in ATTRIBUTES.items():
        assert isinstance(getattr(model, name), kind), name
    for name in METHODS["any"] + tuple(
            m for k in sorted(kinds) for m in METHODS[k]):
        assert callable(getattr(model, name, None)), \
            "{} of a builder that {} cells run".format(name, sorted(kinds))
    json.dumps(model.sizes())  # noted in the run's log as it is

    # weights from the seed (the driver's are large): the same seed the
    # same weights
    first, again, other = (jax.tree.leaves(model.init_params(seed))
                           for seed in (7, 7, 2 ** 31 + 5))
    assert all(np.array_equal(a, b) for a, b in zip(first, again))
    assert any(not np.array_equal(a, b) for a, b in zip(first, other))

    params = model.init_params(7)
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, model.vocab_size, size=(2, 8)))
    if "serve" in kinds:
        logits = model.reference_logits(params, ids)
        assert logits.shape == (2, 8, model.vocab_size)
        assert logits.dtype == jnp.float32
        held = model.kv_bytes_per_token_layer()
        # at most a key and a value for every query head, in float32
        assert isinstance(held, int)
        assert 0 < held <= 2 * model.n_head * model.head_dim * 4
    if "train" in kinds:
        # a deployment whose state exceeds a chip starts on the host
        assert len(jax.tree.leaves(model.init_params(7, on_host=True))) == \
            len(first)
        loss = float(model.reference_loss(params, ids))
        assert abs(loss - np.log(model.vocab_size)) < 1.0  # random weights
        assert model.train_flops_per_token(8) > 0


def test_gpt2_holds_a_key_and_a_value_for_every_head():
    """What ``decode_attn_roofline`` counted before the builder said it:
    n_head x head_dim x (k, v) x bf16."""
    config = harness.load_json(os.path.join(
        harness.ROOT, "benchmark/configs/gpt2-medium-355m.json"))
    model = harness.load_by_name("model_builders", "gpt2").Model(config)
    assert model.kv_bytes_per_token_layer() == 2 * 16 * 64 * 2
