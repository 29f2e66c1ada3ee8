"""PR 58's configuration, builder, reference, probes, readers and cell: the
``nemotron_h`` family (a ONE-BRANCH stack at full depth: Mamba-2 with eight
groups, ungated relu2 experts of which a chip holds 16 of 128, grouped-query
attention without positions) against the contract a test can hold it to. The
model itself is held to the reference in ``tests/unit/test_nemotron_h.py``;
the cell's stand-in runs with the others in ``test_harness.py``. Pins NEITHER
that its entries stand last in ``BENCHMARK.json`` NOR that a shared reader
lists its cell alone."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import costs, costs_nemotron_h, harness, setup_reduce
from tests.benchmark import test_phi4flash as phi4
from tests.benchmark import test_setup_phases as setup_pins
from tests.benchmark import tiny
from tests.benchmark.test_olmoe import _context, _hand_built
from tests.benchmark.test_scope_reduce import MIXED, US

CELL, CONFIG = "serve-nemotron3nano-decode-closed", \
    "nemotron-3-nano-30b-a3b-ep8"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SOURCE = ("https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/"
          "blob/main/config.json")
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
# The language model's settings as its public config.json gives them.
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2,
    "head_dim": 128, "hidden_size": 2688, "hybrid_override_pattern": PATTERN,
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
    "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "residual_in_fp32": False,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "vocab_size": 131072}
NEW_READERS = {
    "ssm_grouped_update_roofline": ("higher", "state-space mixer"),
    "expert_relu2_roofline": ("higher", "expert feed-forward"),
}
SHARED_READERS = (
    "decode.engine_step_ms", "decode.slot_occupancy_pct",
    "decode.kernel_time_pct", "decode.device_idle_pct",
    "decode.peak_hbm_gib", "decode.kv_move_time_pct", "decode.host_ms_step",
    "decode.step_move_time_pct", "expert_time_pct", "router_time_pct",
    "shared_expert_time_pct", "ssm_time_pct") + tuple(setup_pins.NAMES)
# the sum the configuration's ``reduced_why`` states, and the whole model's
PARAMS, WHOLE = 5_258_420_544, 31_577_940_288

builder = harness.load_by_name("model_builders", "nemotron_h")


@pytest.fixture(scope="module")
def manifest():
    return harness.load_json(harness.MANIFEST)


@pytest.fixture(scope="module")
def config(manifest):
    entry, = [c for c in manifest["configs"] if c["name"] == CONFIG]
    return entry, harness.load_json(os.path.join(harness.ROOT,
                                                 entry["file"]))


def test_every_published_key_is_in_the_configuration_unchanged(config):
    entry, body = config
    cut = {"n_routed_experts", "vocab_size"}
    differs = {k for k, v in PUBLISHED.items() if body.get(k, "absent") != v}
    assert differs == set(body["reduced"]) == set(entry["reduced"]) == cut
    assert entry["source"] == body["source"] == SOURCE
    # FULL depth: the whole pattern, 23 / 23 / 6
    assert body["hybrid_override_pattern"] == PATTERN and len(PATTERN) == 52
    assert [PATTERN.count(k) for k in "ME*"] == [23, 23, 6]
    assert [i for i, k in enumerate(PATTERN) if k == "*"] == \
        [5, 12, 19, 26, 33, 42]
    # the experts held, with the published count and the router's width
    assert body["n_routed_experts"] == 16 == body["experts_held"][1]
    assert body["router_outputs"] == 128 == \
        body["published"]["n_routed_experts"]
    assert body["vocab_size"] * 8 == body["published"]["vocab_size"]
    assert body["vocab_held"] == [0, 16384]
    # no width is cut, and none may ever be listed as cut
    assert not any(k.endswith(("_size", "_dim", "_rank", "_head", "_state"))
                   or k == "num_experts_per_tok" for k in cut - {"vocab_size"})
    assert body["deployment"]["chips"] == 1
    assert body["deployment"]["stands_for_chips"] == 8
    for said in ("positions", "mamba_width", "gated_norm", "expert_form",
                 "router", "recurrent_state", "residual", "mamba_init",
                 "initializer_range", "embed_init_range",
                 "lm_head_init_range", "router_bias_init_range", "near_ties",
                 "weights"):
        assert body["assumed"][said]
    assert "rotary" in body["assumed"]["positions"]
    assert "{:,}".format(PARAMS) in body["reduced_why"]
    assert "{:,}".format(WHOLE) in body["reduced_why"]
    assert "10.52 GB" in body["reduced_why"]


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_configuration_holds_every_number_of_the_catalog_row(config):
    row, = [r for r in map(json.loads, open(CATALOG))
            if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"]
    _, body = config
    assert row["source_url"] == body["source"]
    for key, value in row["config"].items():
        if key not in body["reduced"]:
            assert body[key] == value, key


def test_the_cell_is_one_chip_with_granites_traffic_unchanged(manifest):
    cell = harness.Cell(manifest, CELL)
    granite = harness.Cell(manifest, "serve-granite4h-decode-closed")
    assert cell.chips == 1 and cell.traffic_name == "hybrid-decode-closed"
    assert cell.traffic == granite.traffic
    mix = cell.traffic
    assert (mix["kind"], mix["loop"], mix["clients"], mix["request_pool"],
            mix["schedule_seed"], mix["rate_chunk_steps"],
            mix["trace_steps"]) == ("serve", "closed", 64, 256, 1, 4, 8)
    assert mix["engine"] == {"max_slots": 64, "max_len": 2304,
                             "chunk_size": 16, "paged_kv": True,
                             "kv_page_len": 128, "prefill_chunk": 128}
    # a prompt is one slice of the lane, which is the published chunk
    assert mix["prompt"]["max"] <= mix["engine"]["prefill_chunk"] == \
        cell.config["chunk_size"]
    assert {m["name"] for m in cell.metrics("end_to_end")} == \
        {"serve_tok_s", "setup_s"}
    reports = {m["name"] for m in cell.metrics("per_layer")}
    assert reports == set(NEW_READERS) | set(SHARED_READERS)
    # Granite's keys and counts, and the list pin
    assert not {"ssm_update_roofline", "expert_held_roofline",
                "paged_decode_roofline"} & reports
    assert len(manifest["workloads"]) == 13
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1


@pytest.mark.parametrize("name", sorted(NEW_READERS))
def test_a_new_reader_is_this_cells_alone_under_a_layer_that_exists(
        manifest, name):
    row, = [m for m in manifest["per_layer"] if m["name"] == name]
    better, layer = NEW_READERS[name]
    assert row == {"name": name, "unit": "%", "better": better,
                   "source": "device_trace", "layer": layer,
                   "moves": "serve_tok_s", "workloads": [CELL]}
    assert layer in {m["layer"] for m in manifest["per_layer"]
                     if m["name"] not in NEW_READERS}


def test_the_builder_counts_the_parameters_from_shapes_and_no_weights(
        manifest):
    model = harness.load_model(harness.Cell(manifest, CELL))
    assert model.param_count() == PARAMS          # 10.52 GB in bf16
    expert, shared_, router = 16 * 9_977_856, 19_955_712, 344_064
    e_layer = expert + shared_ + router + 128 + 2_688
    m_layer = 2688 * 10_304 + 6_144 * 5 + 3 * 64 + 4_096 \
        + 4_096 * 2688 + 2_688
    a_layer = 2 * 2688 * 4_096 + 2 * 2688 * 256 + 2_688
    assert (e_layer, m_layer, a_layer) == (179_948_288, 38_744_896,
                                           23_399_040)
    assert 23 * e_layer + 23 * m_layer + 6 * a_layer \
        + 2 * 16_384 * 2688 + 2_688 == PARAMS
    assert 23 * (e_layer + 112 * 9_977_856) + 23 * m_layer + 6 * a_layer \
        + 2 * 131_072 * 2688 + 2_688 == WHOLE
    assert model.kv_bytes_per_token_layer() == 2 * 2 * 128 * 2 == 1024
    assert (model.n_layer, model.n_head, model.head_dim, model.vocab_size) \
        == (52, 32, 128, 16384)
    cfg = model.module.config
    assert cfg.one_branch and cfg.expert_layers == 23
    assert (len(cfg.mamba_layers), cfg.kv_layers) == (
        23, (5, 12, 19, 26, 33, 42))
    assert (cfg.n_experts, cfg.held, cfg.experts_per_token) == \
        (128, (0, 16), 6)
    assert (cfg.rope, cfg.n_kv, cfg.mamba_groups, cfg.expert_act) == \
        (False, 2, 8, "relu2")
    # the one stated departure: the stream in float32 (``residual_dtype``)
    assert cfg.mamba_dt_apart and cfg.stream_dtype == jnp.float32
    assert cfg.dtype == jnp.bfloat16
    shapes = jax.eval_shape(model.module.init, jax.random.PRNGKey(0))[
        "params"]
    # expert stacks as deep as the expert layers only, ONE norm a layer
    assert shapes["moe"]["w_up"].shape == (23, 16, 2688, 1856)
    assert shapes["moe"]["w_down"].shape == (23, 16, 1856, 2688)
    assert set(shapes["layers"]) == {"norm"} and \
        shapes["layers"]["norm"].shape == (52, 2688)
    assert shapes["mamba"]["in_proj"].shape == (23, 2688, 10_240)
    assert shapes["mamba"]["dt_proj"].shape == (23, 2688, 64)
    sizes = model.sizes()
    # 128 x 4096 float32 and a [3, 6144] bf16 tail, 23 layers a slot
    assert sizes["state_bytes_per_slot"] == 23 * (
        128 * 4096 * 4 + 3 * 6144 * 2) == 49_082_368
    assert sizes["stack_layers"] == {"mamba": 23, "moe": 23, "attention": 6}
    json.dumps(sizes)


def test_the_builder_refuses_what_it_does_not_build(config):
    for key, value in (("mlp_hidden_act", "silu"), ("n_group", 8),
                       ("tie_word_embeddings", True),
                       ("n_routed_experts", 128),
                       ("hybrid_override_pattern", "MEM")):
        with pytest.raises(ValueError):
            builder.Model(dict(config[1], **{key: value}))


def test_a_program_without_the_one_branch_stack_fails_at_once(config,
                                                              monkeypatch):
    """The parent commit under this PR's benchmark files: the builder says
    what the program lacks before anything is traced."""
    from deepspeed_tpu.models import decoder

    fields = tuple(f for f in decoder.DecoderConfig._fields
                   if f != "mamba_groups")
    monkeypatch.setattr(decoder.DecoderConfig, "_fields", fields)
    with pytest.raises(RuntimeError, match="one-branch"):
        builder.Model(config[1])


def _tiny_model(dtype=None):
    standin = tiny.standins()[CELL]
    body = harness.load_json(harness._find(
        harness.paths(), "configs", standin["config"] + ".json"))
    assert body["model_type"] == "nemotron_h"
    assert set(PUBLISHED) <= set(body)
    if dtype:
        body = dict(body, deployment=dict(body["deployment"],
                                          compute_dtype=dtype))
    model = builder.Model(body)
    return model, model.init_params(7)


def _streams(model, rows=2, length=48):
    return np.random.RandomState(3).randint(
        0, model.vocab_size, size=(rows, length)).astype(np.int32)


def test_the_program_is_the_reference_at_the_stand_ins_size():
    """float32 at the tiny size: the cache-free pass against the plain
    reference to 2e-4 on logits that spread 0.65."""
    model, params = _tiny_model("float32")
    ids = _streams(model, length=24)
    want = builder.reference_logits(params, ids, model.cfg)
    got = model.module.apply({"params": params}, jnp.asarray(ids))
    assert want.dtype == np.float32 and 0.5 < want.std() < 0.8
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)
    # the vocabulary slice as an argument: the whole table handed over, the
    # reference takes the rows it is told
    names = builder.published_names(params, model.cfg)
    names = dict(names, embeddings=jnp.concatenate(
        [names["embeddings"]] * 2), lm_head=jnp.concatenate(
            [names["lm_head"]] * 2, axis=1))
    again = builder.reference.logits(names, ids, builder.hyper(model.cfg))
    np.testing.assert_array_equal(again, want)


def test_the_sound_program_is_inside_every_limit_and_nothing_is_refused():
    model, params = _tiny_model()
    ids = _streams(model)
    notes = []
    harness_note, harness.note = harness.note, lambda **kw: notes.append(kw)
    try:
        out = model.reference_logits(params, ids)
    finally:
        harness.note = harness_note
    said, = [n for n in notes if n["event"] == "precision"]
    assert said["held"] and said["replayed"]
    assert said["limits"] == {"state_rel_err": builder.STATE_LIMIT,
                              "router_logit_err": builder.ROUTER_LIMIT,
                              "stream_rel_err": builder.STREAM_LIMIT}
    for name, limit in said["limits"].items():
        assert said[name] is not None and said[name] <= limit, name
    assert said["not_followed"] == 0
    assert said["differ"] == said["followed"]
    assert 0 <= said["exempt_positions"] < said["positions"] == ids.size
    assert said["exempt_share"] == said["exempt_positions"] / ids.size
    assert np.abs(out).max() < builder.REFUSED / 2


def _bf16_state(monkeypatch):
    from deepspeed_tpu.models import mamba2

    shapes = mamba2.state_shapes
    monkeypatch.setattr(mamba2, "state_shapes", lambda cfg: tuple(
        (k, s, jnp.bfloat16 if k.startswith("slot_ssm") else d)
        for k, s, d in shapes(cfg)))


def _bf16_router(monkeypatch):
    from deepspeed_tpu.models import decoder

    monkeypatch.setattr(
        decoder, "router_logits", lambda n32, router: jnp.dot(
            n32.astype(jnp.bfloat16), router.astype(jnp.bfloat16)).astype(
                jnp.float32))


def _bf16_stream(monkeypatch):
    error = builder.stream_error
    monkeypatch.setattr(
        builder, "stream_error", lambda stack, cfg, seen: error(
            stack, cfg, seen, jnp.bfloat16))


@pytest.mark.parametrize("plant, reading, limit", [
    (_bf16_state, "state_rel_err", "STATE_LIMIT"),
    (_bf16_router, "router_logit_err", "ROUTER_LIMIT"),
    (_bf16_stream, "stream_rel_err", "STREAM_LIMIT")],
    ids=["bf16_state", "bf16_router", "bf16_stream"])
def test_the_precision_below_the_stated_one_is_not_correct(
        plant, reading, limit, monkeypatch):
    """Each quantity computed a precision lower, put in the program's place:
    its reading passes its limit and no served token is within the driver's
    margin."""
    model, params = _tiny_model()
    ids = _streams(model)
    plant(monkeypatch)
    builder.retrace()
    notes = []
    monkeypatch.setattr(harness, "note", lambda **kw: notes.append(kw))
    try:
        out = model.reference_logits(params, ids)
    finally:
        monkeypatch.undo()
        builder.retrace()
    said, = [n for n in notes if n["event"] == "precision"]
    assert not said["held"] and said[reading] > getattr(builder, limit)
    nxt = np.roll(ids, -1, axis=1)
    margin = out.max(-1) - np.take_along_axis(out, nxt[..., None], -1)[..., 0]
    assert margin.min() > 100


def test_the_reference_follows_the_program_only_inside_the_gap():
    """``Precision.follow`` on logits whose edge is known: a held expert the
    replay kept from 0.01 logits under the edge is followed, one from 0.5
    under is not, and an expert held elsewhere changing sides is neither
    followed nor counted."""
    model, params = _tiny_model()
    cfg, k = model.cfg, model.cfg.experts_per_token
    params = dict(params, moe=dict(params["moe"], router_bias=jnp.zeros_like(
        params["moe"]["router_bias"])))
    logits = np.asarray([[3.0, 2.0, 1.0, 0.99, -1.0, -1.01, -2.0, -3.0],
                         [3.0, 2.0, 1.0, 0.5, -1.0, -1.01, -2.0, -3.0],
                         [3.0, 2.0, -1.0, -2.0, 1.0, 0.99, -2.0, -3.0]],
                        np.float32)
    # the replay kept expert 3 in place of 2 (rows 0, 1); 5 in place of 4
    choices = np.asarray([[0, 1, 3], [0, 1, 3], [0, 1, 2]])
    choices[2] = [0, 1, 5]
    layer = cfg.moe_layers[0]
    held = builder.Precision(params, cfg, np.broadcast_to(
        choices, (cfg.expert_layers, 1) + choices.shape))
    kept = held.follow(layer, 0, logits)
    assert (cfg.held, k) == ((0, 4), 3)
    assert sorted(kept[0]) == [0, 1, 3]         # followed: a near-tie
    assert sorted(kept[1]) == [0, 1, 2]         # not followed: 0.5 away
    assert sorted(kept[2]) == [0, 1, 4]         # held elsewhere: its own
    assert held.parted["differ"] == 2 and held.parted["followed"] == 1
    assert held.parted["not_followed"] == 1
    assert 0 < held.parted["furthest_followed"] < builder.FOLLOW_GAP \
        < held.parted["furthest_parted"]
    # the band: row 0's held experts 2 and 3 stand 0.01 apart: not exempt
    # at 0.003; a held expert ON the edge is
    assert not held.ties((1, 3)).any()
    close = logits.copy()
    close[0, 3] = 0.999
    held.follow(layer, 0, close)
    assert held.ties((1, 3))[0].tolist() == [True, False, False]


# ------------------------------------------------- the costs and the readers


def test_the_grouped_update_is_bound_by_the_state_it_moves():
    nbytes = costs_nemotron_h.ssm_grouped_update_bytes(64, 64, 64, 128, 8)
    state = 64 * 128 * 4096 * 4                     # 134 MB a layer
    assert state == 134_217_728
    assert nbytes == 2 * state + 64 * (3 * 4096 + 2 * 8 * 128) * 4
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    seconds, bound = costs.least_seconds(0.5 * nbytes, nbytes, peaks)
    assert bound == "memory" and seconds == pytest.approx(0.3322e-3,
                                                          rel=1e-3)
    # 23 layers an iteration: 7.6 ms
    assert 23 * seconds == pytest.approx(7.64e-3, rel=0.01)


@pytest.mark.parametrize("rows, touched", [
    (1, 0.75), (64, 16 * (1 - (122 / 128) ** 64)), (100000, 16.0)])
def test_held_relu2_experts_touched_under_uniform_routing(rows, touched):
    got = costs_nemotron_h.experts_touched(rows, 16, 128, 6)
    assert got == pytest.approx(touched) and got <= 16.0
    from benchmark import costs_granitemoehybrid
    assert got == costs_granitemoehybrid.experts_touched(rows, 16, 128, 6)


def test_the_relu2_share_counts_two_matrices_an_expert():
    cost = costs_nemotron_h.expert_relu2_cost(64, 16, 128, 6, 2688, 1856)
    one_expert = 2 * 2688 * 1856 * 2                # TWO matrices, bf16
    assert one_expert == 2 * 9_977_856
    assert cost["experts_touched"] == pytest.approx(15.26, abs=0.01)
    assert cost["bytes"] == pytest.approx(
        cost["experts_touched"] * one_expert + 2 * 64 * 2688 * 2)
    # an eighth of a token's six choices fall here
    assert cost["flops"] == 64 * 6 * 0.125 * 2 * 2 * 2688 * 1856
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    seconds, bound = costs.least_seconds(cost["flops"], cost["bytes"], peaks)
    assert bound == "memory" and seconds == pytest.approx(0.3727e-3,
                                                          rel=0.01)


def _readers():
    return {name: harness.load_by_name("layer_metrics", name)
            for name in NEW_READERS}


def _run(name, mixed, config):
    run = _context(name, _hand_built(name, mixed), config)
    run["counters"]["slots"] = 64
    run["counters"]["n_layer"] = 52
    return run


def test_the_two_readers_on_a_hand_built_trace(manifest):
    """``test_scope_reduce.py``'s trace with the family's words in it: the
    scan's matmul fusion (3 us) under ``mamba/ssm``, then under
    ``moe/experts``; a call a LETTER of the pattern, not a layer of 52."""
    prefix = "jit(mixed_step)/decode_scan/while/body/closed_call/"
    config = dict(harness.Cell(manifest, CELL).config,
                  hybrid_override_pattern="MME*")
    mixed = dict(MIXED, **{"fusion.9": ("fusion", prefix + "mamba/ssm/mul")})
    run = _run("nemotron-hand-built", mixed, config)
    readers = _readers()
    least = costs_nemotron_h.ssm_grouped_update_bytes(
        64, 64, 64, 128, 8) / 819e9
    assert readers["ssm_grouped_update_roofline"].read(run) == \
        pytest.approx(100.0 * 2 * least / (3 * US))
    assert readers["expert_relu2_roofline"].read(run) is None

    mixed = dict(MIXED, **{
        "fusion.9": ("fusion", prefix + "moe/experts/dot_general")})
    run = _run("nemotron-hand-built-experts", mixed, config)
    cost = costs_nemotron_h.expert_relu2_cost(64, 16, 128, 6, 2688, 1856)
    assert readers["expert_relu2_roofline"].read(run) == \
        pytest.approx(100.0 * 1 * (cost["bytes"] / 819e9) / (3 * US))
    assert readers["ssm_grouped_update_roofline"].read(run) is None


def test_the_readers_return_nothing_for_a_program_without_the_regions(
        manifest):
    """A parent commit's trace, or another family's: nothing raises,
    nothing is reported."""
    config = harness.Cell(manifest, CELL).config
    run = _run("nemotron-no-regions", MIXED, config)
    assert {n: r.read(run) for n, r in _readers().items()} == \
        dict.fromkeys(NEW_READERS)
    # and Granite's cell, whose trace has both regions: not this family's
    prefix = "jit(mixed_step)/decode_scan/while/body/closed_call/"
    for region in ("moe/experts/dot_general", "mamba/ssm/mul"):
        mixed = dict(MIXED, **{"fusion.9": ("fusion", prefix + region)})
        granite = harness.Cell(manifest,
                               "serve-granite4h-decode-closed").config
        run = _run("nemotron-other-family", mixed, granite)
        assert {n: r.read(run) for n, r in _readers().items()} == \
            dict.fromkeys(NEW_READERS)


def test_the_familys_region_words_are_the_ones_the_readers_know():
    names = harness.load_json(harness._find(
        harness.paths(), "names", "nemotron_h.json"))
    granite = harness.load_json(harness._find(
        harness.paths(), "names", "granitemoehybrid.json"))
    assert names["scopes"] == granite["scopes"]
    assert not set(names) & {"kernels", "classes"}


# ----------------------------- what the pins of PRs 53 and 55 held, beside
# this PR's cell (``tests/conftest.py`` PINS_AS_PR_55_LEFT_THEM: the twelve
# cases of ``test_phi4flash.py`` whose two stale lines an appended cell and two
# appended metrics make untrue; every other line of them, case for case)


@pytest.mark.parametrize("name", setup_pins.NAMES)
def test_a_setup_row_stands_as_pr_55_left_it_and_lists_this_cell(name):
    manifest = harness.load_json(harness.MANIFEST)
    rows = [m for m in manifest["per_layer"] if m["name"] == name]
    assert len(rows) == 1
    counter = name in ("setup_programs", "setup_cache_misses")
    # the eleven where they stood, PR 55's cell after them, this PR's last
    assert rows[0] == {
        "name": name, "unit": "programs" if counter else "s",
        "better": "lower",
        "source": "program_counter" if counter else "program_span",
        "layer": "start-up", "moves": "setup_s",
        "workloads": setup_pins.CELLS + [phi4.CELL, CELL]}
    assert set(setup_pins.CELLS) <= {w["name"]
                                     for w in manifest["workloads"]}
    setup = [m for m in manifest["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]
    assert setup_reduce.reading({"setup_phases": {setup_pins.PART[name]: 7}},
                                setup_pins.PART[name]) == 7


@pytest.mark.parametrize("pin", [setup_pins._dsv3, setup_pins._kimi,
                                 setup_pins._lfm2, setup_pins._sdar],
                         ids=lambda pin: pin.__name__.strip("_"))
def test_a_pin_of_a_cells_exact_set_holds_beside_the_eight_five_and_two(pin):
    """The four pins of a cell's EXACT per-layer set, called themselves on
    the manifest without PR 53's eight rows; what stood before the eight
    stands where it stood, the eight after it, PR 55's five after them, this
    PR's two last (none of which any other cell lists)."""
    before = setup_pins._without_the_eight()
    cell = pin(before)
    now = harness.load_json(harness.MANIFEST)
    assert {m["name"] for m in harness.Cell(now, cell).metrics("per_layer")} \
        == {m["name"] for m in harness.Cell(before, cell).metrics(
            "per_layer")} | set(setup_pins.NAMES)
    later = set(phi4.NEW_READERS) | set(NEW_READERS)
    stood = [m for m in before["per_layer"] if m["name"] not in later]
    assert now["per_layer"][:len(stood)] == stood
    assert [m["name"] for m in now["per_layer"][len(stood):]] == \
        list(setup_pins.NAMES) + list(phi4.NEW_READERS) + list(NEW_READERS)
