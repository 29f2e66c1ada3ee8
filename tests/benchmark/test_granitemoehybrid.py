"""The Granite 4.0-H family's own benchmark files: its configuration against
the published keys, its cell's traffic, its builder against the reference at
the stand-in's size, its costs by hand, and its four readers on a hand-built
trace and on one that lacks the family's regions (a parent commit's)."""

import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import costs, costs_granitemoehybrid, harness
from tests.benchmark import tiny
from tests.benchmark.test_olmoe import _context, _hand_built
from tests.benchmark.test_scope_reduce import MIXED, US

CELL = "serve-granite4h-decode-closed"
CONFIG = "granite-4.0-h-small-10l-ep2"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
READERS = ("ssm_time_pct", "ssm_update_roofline", "expert_held_roofline",
           "shared_expert_time_pct")
# The language model's settings as its public config.json gives them.
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.0078125,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 4096,
    "intermediate_size": 768, "logits_scaling": 16, "mamba_chunk_size": 256,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
    "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_n_heads": 128, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm", "num_attention_heads": 32,
    "num_experts_per_tok": 10, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "num_local_experts": 72,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 1536, "tie_word_embeddings": True,
    "vocab_size": 100352}
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4


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
    cut = {"num_hidden_layers", "layer_types", "num_local_experts",
           "vocab_size"}
    differs = {k for k, v in PUBLISHED.items() if body.get(k, "absent") != v}
    assert differs | {"layer_types"} == set(body["reduced"]) == \
        set(entry["reduced"]) == cut
    assert entry["source"] == body["source"]
    # one whole period, both kinds in the published 9:1
    assert body["layer_types"] == PERIOD and body["num_hidden_layers"] == 10
    # the experts held, with the published count and the router's width
    assert body["num_local_experts"] == 36 == body["experts_held"][1]
    assert body["router_outputs"] == 72 == \
        body["published"]["num_local_experts"]
    assert body["vocab_size"] * 2 == body["published"]["vocab_size"]
    # no width is cut, and none may ever be listed as cut
    assert not any(k.endswith(("_size", "_dim", "_rank", "_head", "_state"))
                   or k == "num_experts_per_tok" for k in cut - {"vocab_size"})
    assert body["deployment"]["chips"] == 1
    assert body["deployment"]["stands_for_chips"] == 8
    for said in ("head_dim", "intermediate_size", "router", "recurrent_state",
                 "mamba_init", "initializer_range", "embed_init_range",
                 "final_norm_init", "weights"):
        assert body["assumed"][said]
    assert "9.51 GB" in body["reduced_why"]


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_configuration_holds_every_number_of_the_catalog_row(config):
    import json

    row, = [r for r in map(json.loads, open(CATALOG))
            if r["name"] == "granite-4.0-h-small"]
    _, body = config
    assert row["source_url"] == body["source"]
    for key, value in row["config"].items():
        if key not in body["reduced"]:
            assert body[key] == value, key
    assert body["layer_types"] == row["config"]["layer_types"][:10]


def test_the_cell_is_one_chip_with_the_issues_traffic(manifest):
    cell = harness.Cell(manifest, CELL)
    assert cell.chips == 1 and cell.traffic_name == "hybrid-decode-closed"
    mix = cell.traffic
    assert (mix["kind"], mix["loop"], mix["clients"], mix["request_pool"],
            mix["sampling"], mix["tokens"], mix["rate_chunk_steps"]) == (
                "serve", "closed", 64, 256, "stratified", "uniform", 4)
    assert mix["prompt"] == {"median": 64, "sigma": 0.5, "min": 32,
                             "max": 128}
    assert mix["output"] == {"median": 1408, "sigma": 0.3, "min": 1024,
                             "max": 2176}
    assert mix["engine"] == {"max_slots": 64, "max_len": 2304,
                             "chunk_size": 16, "paged_kv": True,
                             "kv_page_len": 128, "prefill_chunk": 128}
    assert "schedule_seed" in mix
    # the longest request fits a slot, and the shortest outlives the slots
    assert mix["prompt"]["max"] + mix["output"]["max"] <= \
        mix["engine"]["max_len"]
    assert mix["output"]["min"] / mix["engine"]["chunk_size"] >= \
        mix["engine"]["max_slots"]
    assert {m["name"] for m in cell.metrics("end_to_end")} == \
        {"serve_tok_s", "setup_s"}
    reports = {m["name"] for m in cell.metrics("per_layer")}
    assert set(READERS) | {"expert_time_pct", "router_time_pct",
                           "decode.kv_move_time_pct",
                           "decode.step_move_time_pct"} <= reports
    # their readers count every expert as held / every layer as holding keys
    assert not {"expert_ffn_roofline", "decode.decode_attn_roofline"} & reports
    layers = {m["name"]: m["layer"] for m in manifest["per_layer"]}
    assert layers["ssm_time_pct"] == layers["ssm_update_roofline"] == \
        "state-space mixer"
    assert layers["expert_held_roofline"] == \
        layers["shared_expert_time_pct"] == layers["expert_time_pct"]


def test_the_builder_counts_the_cache_the_state_and_the_parameters(manifest):
    model = harness.load_model(harness.Cell(manifest, CELL))
    # a key and a value for 8 stored heads of 128 in bf16, in the ONE layer
    assert model.kv_bytes_per_token_layer() == 2 * 8 * 128 * 2 == 4096
    assert (model.n_layer, model.n_head, model.head_dim, model.vocab_size) \
        == (10, 32, 128, 50176)
    cfg = model.module.config
    assert (cfg.n_experts, cfg.held, cfg.experts_per_token) == \
        (72, (0, 36), 10)
    assert cfg.kv_layers == (5,) and len(cfg.mamba_layers) == 9
    assert (cfg.rope, cfg.attn_scale, cfg.n_kv) == (False, 0.0078125, 8)
    sizes = model.sizes()
    mamba, attention = 102_286_976, 41_943_040
    every = 18_874_368 + 294_912 + 8_192 + 339_738_624
    assert sizes["params"] == 9 * mamba + attention + 10 * every \
        + 50176 * 4096 + 4096              # 4,757M parameters: 9.51 GB
    assert round(sizes["params"] * 2 / 1e9, 2) == 9.51
    # 128 x 64 x 128 float32 and a [3, 8448] bf16 tail, nine layers a slot
    assert sizes["state_bytes_per_slot"] == 9 * (
        128 * 64 * 128 * 4 + 3 * 8448 * 2) == 37_748_736 + 456_192
    spec = model.module_cache_spec()
    assert (spec.n_layer, spec.n_head, spec.n_embd) == (1, 8, 1024)


def test_the_builder_refuses_what_it_does_not_build(config):
    build = harness.load_by_name("model_builders", "granitemoehybrid").Model
    for key, value in (("mamba_n_groups", 8),
                       ("position_embedding_type", "rope"),
                       ("num_local_experts", 72),
                       ("layer_types", ["mamba"] * 9 + ["window"])):
        with pytest.raises(ValueError):
            build(dict(config[1], **{key: value}))


def test_the_program_is_the_reference_at_the_stand_ins_size():
    """float32 at the tiny size: the cache-free pass against the plain
    reference to 2e-4 on logits that spread 1 (the order of the sums)."""
    standin = tiny.standins()[CELL]
    body = harness.load_json(harness._find(
        harness.paths(), "configs", standin["config"] + ".json"))
    assert body["model_type"] == "granitemoehybrid"
    assert set(PUBLISHED) <= set(body)
    body = dict(body, deployment=dict(body["deployment"],
                                      compute_dtype="float32"))
    model = harness.load_by_name("model_builders",
                                 "granitemoehybrid").Model(body)
    params = model.init_params(11)
    ids = np.random.RandomState(0).randint(0, model.vocab_size, size=(2, 24))
    want, gaps = harness.load_by_name(
        "model_builders", "granitemoehybrid").reference_logits(
            params, ids, model.cfg, with_gaps=True)
    got = model.module.apply({"params": params}, jnp.asarray(ids))
    assert want.dtype == np.float32 and want.std() > 0.5
    assert gaps.shape == (2, 24) and (gaps >= 0).all()
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)


def test_the_state_update_is_bound_by_the_state_it_moves():
    nbytes = costs_granitemoehybrid.ssm_update_bytes(64, 128, 64, 128)
    state = 64 * 128 * 64 * 128 * 4                 # 268 MB a layer
    assert state == 268_435_456
    assert nbytes == 2 * state + 64 * (3 * 8192 + 2 * 128) * 4
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    seconds, bound = costs.least_seconds(0.5 * nbytes, nbytes, peaks)
    assert bound == "memory" and seconds == pytest.approx(0.6635e-3, rel=1e-3)
    # nine layers an iteration: the 5.9 ms of the cell's why
    assert 9 * seconds == pytest.approx(5.97e-3, rel=0.01)


@pytest.mark.parametrize("rows, touched", [
    (1, 5.0), (64, 36 * (1 - (62 / 72) ** 64)), (100000, 36.0)])
def test_held_experts_touched_under_uniform_routing(rows, touched):
    got = costs_granitemoehybrid.experts_touched(rows, 36, 72, 10)
    assert got == pytest.approx(touched) and got <= 36.0
    # all of them held is OLMoE's count
    from benchmark import costs_olmoe
    assert costs_granitemoehybrid.experts_touched(rows, 64, 64, 8) == \
        pytest.approx(costs_olmoe.experts_touched(rows, 64, 8))


def test_the_held_share_is_bound_by_its_weights_at_decode_shapes():
    cost = costs_granitemoehybrid.expert_held_cost(64, 36, 72, 10, 4096, 768)
    one_expert = 3 * 4096 * 768 * 2
    assert cost["experts_touched"] == pytest.approx(36.0, abs=0.01)
    assert cost["bytes"] == pytest.approx(
        cost["experts_touched"] * one_expert + 2 * 64 * 4096 * 2)
    # half of a token's ten choices fall here
    assert cost["flops"] == 64 * 10 * 0.5 * 3 * 2 * 4096 * 768
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    seconds, bound = costs.least_seconds(cost["flops"], cost["bytes"], peaks)
    assert bound == "memory" and seconds == pytest.approx(0.831e-3, rel=0.01)


def _readers():
    return {name: harness.load_by_name("layer_metrics", name)
            for name in READERS}


def _run(name, mixed, config):
    run = _context(name, _hand_built(name, mixed), config)
    run["counters"]["slots"] = 64
    run["counters"]["n_layer"] = 1
    return run


def test_the_four_readers_on_a_hand_built_trace(manifest):
    """``test_scope_reduce.py``'s trace with the family's words in it: the
    scan's matmul fusion (3 us) under ``mamba/ssm``, its movement fusion
    (3 us) under ``moe/shared``, and the lane's fusion under
    ``moe/experts`` (not in the scan: the roofline does not read it)."""
    prefix = "jit(mixed_step)/decode_scan/while/body/closed_call/"
    mixed = dict(MIXED, **{
        "fusion.9": ("fusion", prefix + "mamba/ssm/mul"),
        "slice_bitcast_fusion.2": ("fusion", prefix + "moe/shared/dot")})
    config = dict(harness.Cell(manifest, CELL).config,
                  layer_types=["mamba"])
    run = _run("granite-hand-built", mixed, config)
    busy = run["trace"]["busy_s"]
    readers = _readers()
    assert readers["ssm_time_pct"].read(run) == \
        pytest.approx(100.0 * 3 * US / busy)
    assert readers["shared_expert_time_pct"].read(run) == \
        pytest.approx(100.0 * 3 * US / busy)
    least = costs_granitemoehybrid.ssm_update_bytes(64, 128, 64, 128) / 819e9
    assert readers["ssm_update_roofline"].read(run) == \
        pytest.approx(100.0 * least / (3 * US))
    assert readers["expert_held_roofline"].read(run) is None

    mixed = dict(MIXED, **{
        "fusion.9": ("fusion", prefix + "moe/experts/dot_general")})
    run = _run("granite-hand-built-experts", mixed, config)
    cost = costs_granitemoehybrid.expert_held_cost(64, 36, 72, 10, 4096, 768)
    assert readers["expert_held_roofline"].read(run) == \
        pytest.approx(100.0 * (cost["bytes"] / 819e9) / (3 * US))
    assert readers["ssm_update_roofline"].read(run) is None


def test_the_readers_return_nothing_for_a_program_without_the_regions(
        manifest):
    """A parent commit's trace, or another family's: nothing raises,
    nothing is reported."""
    config = harness.Cell(manifest, CELL).config
    run = _run("granite-no-regions", MIXED, config)
    assert {n: r.read(run) for n, r in _readers().items()} == \
        dict.fromkeys(READERS)
    # and another family's cell, whose configuration states no share
    prefix = "jit(mixed_step)/decode_scan/while/body/closed_call/"
    mixed = dict(MIXED, **{
        "fusion.9": ("fusion", prefix + "moe/experts/dot_general")})
    olmoe = harness.Cell(manifest, "serve-olmoe-decode-closed").config
    run = _run("granite-no-regions", mixed, olmoe)
    assert _readers()["expert_held_roofline"].read(run) is None
    assert _readers()["ssm_update_roofline"].read(run) is None


# ------------------------------------- what holds the stated precision


def _tiny_model():
    standin = tiny.standins()[CELL]
    body = harness.load_json(harness._find(
        harness.paths(), "configs", standin["config"] + ".json"))
    builder = harness.load_by_name("model_builders", "granitemoehybrid")
    model = builder.Model(body)
    return builder, model, model.init_params(7)


def _streams(model, rows=2, length=48):
    return np.random.RandomState(3).randint(
        0, model.vocab_size, size=(rows, length)).astype(np.int32)


def _margin(logits, ids):
    """The serve driver's reading of a stream ``ids`` served whole."""
    picked = np.take_along_axis(logits[:, :-1], ids[:, 1:, None], axis=2)
    return float((logits[:, :-1].max(axis=2) - picked[..., 0]).max())


def test_the_sound_program_is_inside_both_precision_limits():
    """bf16 compute as the cell serves it: on the reference's own inputs the
    program's recurrence and router are the reference's to float32 rounding,
    orders of magnitude inside the limits, and the logits come back as the
    reference gives them."""
    builder, model, params = _tiny_model()
    ids = _streams(model)
    held = builder.Precision(params, model.cfg)
    want = builder.reference_logits(params, ids, model.cfg, watch=held.watch)
    readings = held.readings()
    assert len(held.state) == 2 * 3 and len(held.router) == 2 * 4
    assert 0 < readings["state_rel_err"] < builder.STATE_LIMIT / 10
    assert readings["router_logit_err"] < builder.ROUTER_LIMIT / 10
    assert held.ok()
    np.testing.assert_array_equal(model.reference_logits(params, ids), want)


def _bf16_state(monkeypatch):
    from deepspeed_tpu.models import mamba2

    real = mamba2.state_shapes
    monkeypatch.setattr(mamba2, "state_shapes", lambda cfg: tuple(
        (name, shape, jnp.bfloat16 if name.startswith("slot_ssm") else dtype)
        for name, shape, dtype in real(cfg)))
    return "state_rel_err"


def _bf16_router(monkeypatch):
    from deepspeed_tpu.models import decoder

    monkeypatch.setattr(decoder, "router_logits", lambda n32, router: jnp.dot(
        n32.astype(jnp.bfloat16), router.astype(jnp.bfloat16)).astype(
            jnp.float32))
    return "router_logit_err"


@pytest.mark.parametrize("lower, limit", [(_bf16_state, "STATE_LIMIT"),
                                          (_bf16_router, "ROUTER_LIMIT")])
def test_the_precision_below_the_stated_one_is_not_correct(monkeypatch,
                                                           lower, limit):
    """The recurrent state kept in bf16, or the router's matmul in bf16: the
    comparison reads over its limit (the other stays inside its own) and no
    token of the logits handed to the driver is within its margin."""
    builder, model, params = _tiny_model()
    ids = _streams(model)
    sound = builder.reference_logits(params, ids, model.cfg)
    reading = lower(monkeypatch)
    held = builder.Precision(params, model.cfg)
    builder.reference_logits(params, ids, model.cfg, watch=held.watch)
    readings = held.readings()
    assert readings[reading] > 2 * getattr(builder, limit)
    other, = set(readings) - {reading}
    assert readings[other] < builder.ROUTER_LIMIT / 10
    assert not held.ok()
    got = model.reference_logits(params, ids)
    assert _margin(got, ids) > builder.REFUSED / 2
    # the stream the sound reference prefers is refused as well
    best = np.concatenate([ids[:, :1], sound.argmax(axis=2)[:, :-1]], axis=1)
    assert _margin(model.reference_logits(params, best), best) \
        > builder.REFUSED / 2


def test_refused_logits_put_every_position_outside_the_margin():
    builder = harness.load_by_name("model_builders", "granitemoehybrid")
    rng = np.random.RandomState(0)
    logits = rng.randn(2, 9, 11).astype(np.float32)
    ids = rng.randint(0, 11, size=(2, 9))
    out = builder.refused(logits.copy(), ids)
    picked = np.take_along_axis(out[:, :-1], ids[:, 1:, None], axis=2)[..., 0]
    assert ((out[:, :-1].max(axis=2) - picked) > builder.REFUSED - 10).all()
    assert (out != logits).sum() == 2 * 9


def test_a_layers_experts_are_sliced_out_one_at_a_time():
    builder = harness.load_by_name("model_builders", "granitemoehybrid")
    stack = jnp.arange(2 * 5 * 3 * 8, dtype=jnp.float32).reshape(2, 5, 3, 8)
    up = builder.Experts(stack, 1, slice(4, 8))
    assert len(up) == 5 and len(up[1:3]) == 2
    np.testing.assert_array_equal(up[4], stack[1, 4, :, 4:])
    np.testing.assert_array_equal(up[1:3][1], stack[1, 2, :, 4:])
