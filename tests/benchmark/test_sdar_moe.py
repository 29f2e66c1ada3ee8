"""PR 51's configuration, builder, reference, driver and cell: the
``sdar_moe`` family (generation by diffusion over blocks) against the contract
a test can hold it to. Pins NEITHER that its entries stand last in
``BENCHMARK.json`` NOR that a shared reader lists its cell alone: later cells
are appended after it and may list themselves."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.reference import sdar_moe as reference
from tests.benchmark import tiny

CELL, CONFIG = "serve-sdar-diffusion-closed", "sdar-30b-a3b-chat-6l"
NEW_READERS = {
    "tokens_per_pass": ("tokens", "higher", "program_counter",
                        "serving engine"),
    "commit_pass_pct": ("%", "lower", "program_counter", "serving engine"),
    "unmask_time_pct": ("%", "lower", "device_trace", "kernels"),
    "block_attn_roofline": ("%", "higher", "device_trace", "kernels"),
    "expert_rows_roofline": ("%", "higher", "device_trace", "kernels"),
}
SHARED_READERS = (
    "decode.engine_step_ms", "decode.slot_occupancy_pct",
    "decode.kernel_time_pct", "decode.device_idle_pct",
    "decode.peak_hbm_gib", "decode.kv_move_time_pct", "decode.host_ms_step",
    "decode.step_move_time_pct", "expert_time_pct", "router_time_pct")


@pytest.fixture(scope="module")
def manifest():
    return harness.load_json(harness.MANIFEST)


@pytest.fixture(scope="module")
def builder():
    return harness.load_by_name("model_builders", "sdar_moe")


@pytest.fixture(scope="module")
def tiny_model(builder):
    config = harness.load_json(os.path.join(
        harness.ROOT, "tests/benchmark/configs/sdar-tiny.json"))
    model = builder.Model(config)
    return model, model.init_params(7)


def test_the_cell_its_configuration_and_its_readers_are_entered(manifest):
    config, = [c for c in manifest["configs"] if c["name"] == CONFIG]
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["source"] == ("https://huggingface.co/JetLM/"
                                "SDAR-30B-A3B-Chat/blob/main/config.json")
    cell, = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "diffusion-decode-closed", 1)
    metrics = {m["name"]: m for m in
               manifest["end_to_end"] + manifest["per_layer"]}
    assert CELL in metrics["serve_tok_s"]["workloads"]
    for name in SHARED_READERS:
        assert CELL in metrics[name]["workloads"], name
    for name, (unit, better, source, layer) in NEW_READERS.items():
        m = metrics[name]
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"]) == (unit, better, source, layer, "serve_tok_s")
        assert CELL in m["workloads"]
    # a reader whose iterations it cannot count, and one pinned elsewhere
    assert CELL not in metrics["paged_decode_roofline"]["workloads"]
    assert CELL not in metrics["expert_stream_roofline"]["workloads"]


def test_the_file_holds_every_published_key_but_the_depth():
    """The catalog's ``config`` for SDAR-30B-A3B-Chat, key for key."""
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 32768, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "sdar_moe",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    body = harness.load_json(os.path.join(
        harness.ROOT, "benchmark/configs", CONFIG + ".json"))
    differ = {k for k, v in published.items() if body.get(k) != v}
    assert differ == {"num_hidden_layers"} == set(body["reduced"])
    assert body["num_hidden_layers"] == 6
    assert body["deployment"]["chips"] == 1
    assert body["deployment"]["stands_for_chips"] == 8
    for key in ("block_length", "mask_token_id", "logits", "remasking",
                "qk_norm", "router", "weights", "final_norm_init"):
        assert body["assumed"][key], key
    assert 0 <= body["mask_token_id"] < body["vocab_size"]


def test_the_traffic_is_the_issues_letter_for_letter():
    mix = harness.load_json(os.path.join(
        harness.ROOT, "benchmark/workloads/diffusion-decode-closed.json"))
    assert (mix["kind"], mix["loop"], mix["clients"], mix["request_pool"],
            mix["sampling"], mix["tokens"]) == \
        ("serve_diffusion", "closed", 64, 256, "stratified", "uniform")
    assert mix["prompt"] == {"median": 64, "sigma": 0.5, "min": 32,
                             "max": 128}
    assert mix["output"] == {"median": 2048, "sigma": 0, "min": 2048,
                             "max": 2048}
    assert (mix["block_length"], mix["denoising_steps"]) == (4, 2)
    assert mix["engine"] == {
        "max_slots": 64, "max_len": 2304, "chunk_size": 16, "paged_kv": True,
        "kv_page_len": 128, "prefill_chunk": 128, "max_queue": 64}
    assert (mix["trace_steps"], mix["rate_chunk_steps"]) == (8, 4)
    assert "schedule_seed" in mix


def test_the_builders_tree_counts_the_cells_parameters(builder):
    """4,361,055,744 at the cell's size, from shapes alone (no weights)."""
    model = builder.Model(harness.load_json(os.path.join(
        harness.ROOT, "benchmark/configs", CONFIG + ".json")))
    assert model.sizes()["params"] == 4361055744 == \
        6 * 623120640 + 2 * 311164928 + 2048
    assert model.kv_bytes_per_token_layer() == 2 * 4 * 128 * 2
    assert (model.cfg.block_length, model.cfg.qk_norm, model.cfg.n_kv,
            model.cfg.norm_topk_prob) == (4, "head", 4, True)


def test_the_builder_gives_what_its_driver_and_the_readers_ask(tiny_model):
    """``test_builders.py``'s table for a builder a ``serve`` cell runs, held
    here for the kind it does not know (tests/conftest.py), and what the
    ``serve_diffusion`` driver asks beside it."""
    model, params = tiny_model
    for name, kind in (("module", object), ("vocab_size", int),
                       ("n_layer", int), ("n_head", int), ("head_dim", int),
                       ("block_length", int)):
        assert isinstance(getattr(model, name), kind), name
    for name in ("sizes", "init_params", "reference_logits",
                 "kv_bytes_per_token_layer", "pass_readings"):
        assert callable(getattr(model, name))
    json.dumps(model.sizes())
    first, again, other = (jax.tree.leaves(model.init_params(seed))
                           for seed in (7, 7, 2 ** 31 + 5))
    assert all(np.array_equal(a, b) for a, b in zip(first, again))
    assert any(not np.array_equal(a, b) for a, b in zip(first, other))
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, model.vocab_size, size=(2, 8)))
    logits = model.reference_logits(params, ids)
    assert logits.shape == (2, 8, model.vocab_size)
    assert logits.dtype == jnp.float32
    held = model.kv_bytes_per_token_layer()
    assert isinstance(held, int)
    assert 0 < held <= 2 * model.n_head * model.head_dim * 4


def test_the_reference_is_plain_and_independent():
    with open(reference.__file__) as f:
        text = f.read()
    assert "import deepspeed_tpu" not in text
    assert "from deepspeed_tpu" not in text
    assert 'default_matmul_precision("highest")' in text


def test_the_expert_loop_is_the_literal_sum_over_a_tokens_experts(
        builder, tiny_model):
    model, params = tiny_model
    layer = next(builder.published_names(params, model.cfg)["layers"]())
    n2 = jnp.asarray(np.random.RandomState(1).randn(6, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        kept, _ = reference._router(n2, layer, 2)
        got = reference._moe(n2, layer, kept)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(reference.moe_per_token(n2, layer, 2)),
        atol=1e-6)
    np.testing.assert_allclose(np.asarray(kept).sum(axis=1), 1.0, atol=1e-6)
    assert ((np.asarray(kept) > 0).sum(axis=1) == 2).all()


def test_the_router_follows_a_near_tie_and_nothing_further(builder,
                                                           tiny_model):
    model, params = tiny_model
    layer = next(builder.published_names(params, model.cfg)["layers"]())
    n2 = jnp.asarray(np.random.RandomState(2).randn(16, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        own, seen = reference._router(n2, layer, 2)
        logits = np.asarray(n2 @ jnp.asarray(layer["gate"], jnp.float32))
        order = np.argsort(-logits, axis=1)
        swapped = np.stack([order[:, 0], order[:, 2]], axis=1)
        kept, told = reference._router(
            n2, layer, 2, follow=jnp.asarray(swapped),
            follow_gap=float(np.median(seen["gap"])))
    near = np.asarray(told["followed"])
    assert np.asarray(told["differ"]).all() and near.any() and not near.all()
    picked = np.asarray(kept) > 0
    assert (picked[near, order[near, 2]]).all()
    assert (picked[~near, order[~near, 1]]).all()
    np.testing.assert_allclose(np.asarray(kept)[~near],
                               np.asarray(own)[~near], atol=1e-7)


def test_the_record_gives_every_pass_its_state(builder):
    first, ids, when = builder.states_of(
        prompt=np.arange(6), tokens=[10, 11, 12, 13, 14, 15, 16],
        passes=[1, 0, 0, 1, 1, 0, 0], length=4, steps=2)
    assert first == 4
    np.testing.assert_array_equal(
        ids, [[4, 5, 10, 11], [12, 13, 14, 15], [16, 0, 0, 0]])
    np.testing.assert_array_equal(
        when, [[-1, -1, 1, 0], [0, 1, 1, 0], [0, 2, 2, 2]])


def test_a_pass_that_breaks_the_rule_is_not_held():
    """The driver's reading of a record: a token the reference does not
    choose, a passed-over position more confident than a chosen one, and a
    pass that unmasked another count than the rule's."""
    driver = harness.load_by_name("drivers", "serve_diffusion")
    when = np.asarray([[0, 1, 0, 1], [1, 0, 0, 1]])
    conf = np.log(np.asarray([[[.4, .1, .3, .2], [.1, .4, .3, .2]],
                              [[0, .1, 0, .2], [.1, 0, 0, .2]]]) + 1e-9)
    sound = {"when": when, "margin": np.zeros((2, 4), np.float32),
             "confidence": conf, "exempt": np.zeros((2, 2, 4), bool)}
    assert driver.held(sound, 2, 2) == (0.0, 0.0, 0, 8, 0)
    wrong = dict(sound, margin=np.where(when == 0, 0.3, 0).astype(np.float32))
    assert driver.held(wrong, 2, 2)[0] == pytest.approx(0.3)
    order = dict(sound, confidence=conf[:, :, ::-1])
    assert driver.held(order, 2, 2)[1] > driver.CONFIDENCE_TOL
    count = dict(sound, when=np.asarray([[0, 0, 0, 1], [1, 0, 0, 1]]))
    assert driver.held(count, 2, 2)[2] == 1
    cut = dict(sound, when=np.asarray([[0, 1, 0, 1], [1, 0, 2, 2]]))
    assert driver.held(cut, 2, 2)[3] == 4       # the cut block is left out


def test_the_stand_in_is_the_cells(manifest):
    standin = tiny.standins()[CELL]
    assert standin["cases"] == ["untraced", "traced"]
    mine = {m["name"] for m in manifest["per_layer"]
            if CELL in m.get("workloads", [CELL])}
    assert set(NEW_READERS) | set(SHARED_READERS) == mine
    assert set(standin["traced_readings"]) | set(standin["absent_on_cpu"]) \
        <= mine
