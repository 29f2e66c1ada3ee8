"""The OLMoE family's own benchmark files: its costs, its three readers (on a
hand-built trace, and on one that lacks the family's regions, as a parent
commit's does), its configuration against the published keys, its traffic."""

import os

import pytest
from jax.profiler import ProfileData

from benchmark import costs, costs_olmoe, harness, trace_reduce
from tests.benchmark import tiny
from tests.benchmark.test_scope_reduce import MIXED, US, trace_text

CELL = "serve-olmoe-decode-closed"
# The language model's settings as its public config.json gives them (the
# catalog beside the model-configs guide): every one must stand in the
# configuration file unchanged unless ``reduced`` lists it.
PUBLISHED = {
    "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 1024,
    "max_position_embeddings": 4096, "model_type": "olmoe",
    "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 16,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304}


@pytest.fixture(scope="module")
def manifest():
    return harness.load_json(harness.MANIFEST)


def test_every_published_key_is_in_the_configuration_unchanged(manifest):
    entry = [c for c in manifest["configs"] if c["name"] == "olmoe-1b-7b-8l"]
    assert len(entry) == 1 and entry[0]["reduced"] == ["num_hidden_layers"]
    config = harness.load_json(os.path.join(harness.ROOT, entry[0]["file"]))
    differs = {k for k, v in PUBLISHED.items() if config.get(k, "absent") != v}
    assert differs == set(config["reduced"]) == {"num_hidden_layers"}
    assert config["num_hidden_layers"] == 8
    # no width is cut, and none may ever be listed as cut
    assert not any(k.endswith(("_size", "_dim", "_rank")) or "expert" in k
                   for k in config["reduced"])
    assert config["deployment"]["chips"] == 1
    for said in ("head_dim", "intermediate_size", "router",
                 "initializer_range", "weights"):
        assert config["assumed"][said]


def test_the_cell_is_one_chip_with_the_issues_traffic(manifest):
    cell = harness.Cell(manifest, CELL)
    assert cell.chips == 1 and cell.traffic_name == "moe-decode-closed"
    mix = cell.traffic
    assert (mix["kind"], mix["loop"], mix["clients"], mix["request_pool"],
            mix["sampling"], mix["tokens"]) == (
                "serve", "closed", 32, 128, "stratified", "uniform")
    assert mix["prompt"] == {"median": 64, "sigma": 0.5, "min": 32,
                             "max": 128}
    assert mix["output"] == {"median": 768, "sigma": 0.4, "min": 512,
                             "max": 1536}
    assert mix["engine"] == {"max_slots": 32, "max_len": 2048,
                             "chunk_size": 16, "paged_kv": True,
                             "kv_page_len": 128, "prefill_chunk": 128}
    assert "schedule_seed" in mix
    # the longest request fits a slot, and a request outlives the slots
    assert mix["prompt"]["max"] + mix["output"]["max"] <= \
        mix["engine"]["max_len"]
    assert mix["output"]["median"] / mix["engine"]["chunk_size"] >= \
        mix["engine"]["max_slots"]
    reports = {m["name"] for m in cell.metrics("per_layer")}
    assert {"expert_time_pct", "router_time_pct",
            "expert_ffn_roofline"} <= reports
    layers = {m["layer"] for m in manifest["per_layer"]
              if m["name"] in ("expert_time_pct", "router_time_pct",
                               "expert_ffn_roofline")}
    assert layers == {"expert feed-forward"}


def test_the_builder_counts_the_cache_and_the_parameters(manifest):
    model = harness.load_model(harness.Cell(manifest, CELL))
    assert model.kv_bytes_per_token_layer() == 2 * 16 * 128 * 2 == 8192
    assert (model.n_layer, model.n_head, model.head_dim) == (8, 16, 128)
    # 8 x 419.6 M + 2 x 103.0 M + the final norm = 3.56 B, 7.13 GB in bf16
    assert model.sizes()["params"] == 8 * 419_569_664 + 2 * 103_022_592 \
        + 2048
    assert model.module.config.n_experts == 64
    assert model.module.config.experts_per_token == 8


@pytest.mark.parametrize("rows, touched", [
    (1, 8.0), (32, 64 * (1 - 0.875 ** 32)), (128, 64 * (1 - 0.875 ** 128)),
    (100000, 64.0)])
def test_experts_touched_under_uniform_routing(rows, touched):
    assert costs_olmoe.experts_touched(rows, 64, 8) == pytest.approx(touched)
    assert costs_olmoe.experts_touched(rows, 64, 8) <= 64.0


def test_the_expert_feed_forward_is_bound_by_its_weights_at_decode_shapes():
    cost = costs_olmoe.expert_ffn_cost(32, 64, 8, 2048, 1024)
    one_expert = 3 * 2048 * 1024 * 2
    assert cost["experts_touched"] == pytest.approx(63.1, abs=0.05)   # 98.6%
    assert cost["bytes"] == pytest.approx(
        cost["experts_touched"] * one_expert + 2 * 32 * 2048 * 2)
    assert cost["flops"] == 32 * 8 * 3 * 2 * 2048 * 1024
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    seconds, bound = costs.least_seconds(cost["flops"], cost["bytes"], peaks)
    assert bound == "memory" and seconds == pytest.approx(0.97e-3, rel=0.02)
    # every expert read whole is the most a call can need
    assert cost["bytes"] < 64 * one_expert + 2 * 32 * 2048 * 2


def _hand_built(name, mixed):
    folder = os.path.join(harness.OUT_DIR, "trace", name)
    os.makedirs(folder, exist_ok=True)
    with open(os.path.join(folder, "hand.xplane.pb"), "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(
            trace_text(mixed)))
    return os.path.join(folder, "hand.xplane.pb")


def _context(name, path, config):
    class Cell(object):
        pass

    Cell.name, Cell.config = name, config
    return {"cell": Cell, "trace": trace_reduce.reduce_trace(
        trace_reduce.load(path)),
        "device": {"kind": "TPU v5 lite"},
        "counters": {"trace_steps": 1, "chunk_size": 1, "n_layer": 1,
                     "slots": 32}}


def _readers():
    return {name: harness.load_by_name("layer_metrics", name)
            for name in ("expert_time_pct", "router_time_pct",
                         "expert_ffn_roofline")}


def test_the_three_readers_on_a_hand_built_trace(manifest):
    """``test_scope_reduce.py``'s trace with the family's words in it: the
    scan's matmul fusion (3 us) under ``moe/experts``, its movement fusion
    (3 us) under ``moe/router``."""
    prefix = "jit(mixed_step)/decode_scan/while/body/closed_call/"
    mixed = dict(MIXED, **{
        "fusion.9": ("fusion", prefix + "moe/experts/dot_general"),
        "slice_bitcast_fusion.2": ("fusion", prefix + "moe/router/top_k")})
    config = harness.Cell(manifest, CELL).config
    run = _context("olmoe-hand-built", _hand_built("olmoe-hand-built", mixed),
                   config)
    busy = run["trace"]["busy_s"]
    readers = _readers()
    assert readers["expert_time_pct"].read(run) == \
        pytest.approx(100.0 * 6 * US / busy)
    assert readers["router_time_pct"].read(run) == \
        pytest.approx(100.0 * 3 * US / busy)
    cost = costs_olmoe.expert_ffn_cost(32, 64, 8, 2048, 1024)
    least = cost["bytes"] / 819e9
    assert readers["expert_ffn_roofline"].read(run) == \
        pytest.approx(100.0 * least / (3 * US))


def test_the_readers_return_nothing_for_a_program_without_the_regions(
        manifest):
    """A parent commit's trace, or another family's: no ``moe`` word in any
    program. Nothing raises, nothing is reported."""
    config = harness.Cell(manifest, CELL).config
    run = _context("olmoe-no-regions",
                   _hand_built("olmoe-no-regions", MIXED), config)
    assert {name: r.read(run) for name, r in _readers().items()} == {
        "expert_time_pct": None, "router_time_pct": None,
        "expert_ffn_roofline": None}
    # and a cell of another family, whose configuration has no experts
    gpt2 = harness.Cell(manifest, "serve-gpt2m-decode-closed").config
    run = _context("olmoe-no-regions", _hand_built("olmoe-no-regions", MIXED),
                   gpt2)
    assert _readers()["expert_ffn_roofline"].read(run) is None


def test_the_stand_in_is_the_familys_block_at_a_tiny_size():
    standin = tiny.standins()[CELL]
    config = harness.load_json(harness._find(
        harness.paths(), "configs", standin["config"] + ".json"))
    assert config["model_type"] == "olmoe"
    assert set(PUBLISHED) <= set(config)
    assert config["num_experts_per_tok"] < config["num_experts"]
