"""The DeepSeek-V3 family's own benchmark files: its configuration against
the published keys, its cell's traffic, its builder against the reference at
the stand-in's size, its costs by hand, its three readers on a hand-built
trace and on one that lacks the family's kernel and regions (a parent
commit's), and the two comparisons that hold the stated precision."""

import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import costs, costs_deepseek_v3, costs_granitemoehybrid, \
    harness
from tests.benchmark import tiny
from tests.benchmark.test_olmoe import _context, _hand_built
from tests.benchmark.test_scope_reduce import MIXED, US

CELL = "serve-dsv3-decode-closed"
CONFIG = "deepseek-v3-6l-ep32"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
READERS = ("latent_attn_time_pct", "latent_decode_roofline",
           "expert_share_roofline")
# The language model's settings as its public config.json gives them.
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
    "hidden_act": "silu", "hidden_size": 7168, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v3", "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 128,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 4, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 129280}
CUT = {"num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
       "vocab_size", "num_nextn_predict_layers"}


@pytest.fixture(scope="module")
def manifest():
    return harness.load_json(harness.MANIFEST)


@pytest.fixture(scope="module")
def config(manifest):
    entry, = [c for c in manifest["configs"] if c["name"] == CONFIG]
    return entry, harness.load_json(os.path.join(harness.ROOT,
                                                 entry["file"]))


@pytest.fixture(scope="module")
def model(manifest):
    return harness.load_model(harness.Cell(manifest, CELL))


def test_every_published_key_is_in_the_configuration_unchanged(config):
    entry, body = config
    differs = {k for k, v in PUBLISHED.items() if body.get(k, "absent") != v}
    assert differs == set(body["reduced"]) == set(entry["reduced"]) == CUT
    assert entry["source"] == body["source"]
    assert (body["num_hidden_layers"], body["first_k_dense_replace"]) == (6, 1)
    # the experts held, with the published count and the router's width
    assert body["n_routed_experts"] == 8 == body["experts_held"][1]
    assert body["router_outputs"] == 256 == \
        body["published"]["n_routed_experts"]
    assert body["vocab_size"] * 8 == body["published"]["vocab_size"]
    assert body["published"] == {k: PUBLISHED[k] for k in CUT}
    # no width is cut, and none may ever be listed as cut
    assert not any(k.endswith(("_size", "_dim", "_rank", "_head"))
                   or k == "num_experts_per_tok" for k in CUT - {"vocab_size"})
    assert body["deployment"]["chips"] == 1
    assert body["deployment"]["stands_for_chips"] == 32
    for said in ("router", "norms", "router_bias_init_range",
                 "initializer_range", "lm_head_init_range", "rope_layout",
                 "kv_b_proj", "latent_cache", "weights"):
        assert body["assumed"][said]
    assert "7.48 GB" in body["reduced_why"]
    assert "671.0B" in body["reduced_why"]


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_configuration_holds_every_number_of_the_catalog_row(config):
    import json

    row, = [r for r in map(json.loads, open(CATALOG))
            if r["name"] == "DeepSeek-V3"]
    _, body = config
    assert row["source_url"] == body["source"]
    assert row["config"] == PUBLISHED
    for key, value in row["config"].items():
        if key not in body["reduced"]:
            assert body[key] == value, key


def test_the_cell_is_one_chip_with_the_issues_traffic(manifest):
    cell = harness.Cell(manifest, CELL)
    assert cell.chips == 1 and cell.traffic_name == "latent-decode-closed"
    mix = cell.traffic
    assert (mix["kind"], mix["loop"], mix["clients"], mix["request_pool"],
            mix["sampling"], mix["tokens"], mix["rate_chunk_steps"],
            mix["trace_steps"]) == (
                "serve", "closed", 128, 512, "stratified", "uniform", 4, 8)
    assert mix["prompt"] == {"median": 64, "sigma": 0.5, "min": 32,
                             "max": 128}
    assert mix["output"] == {"median": 2304, "sigma": 0.15, "min": 2048,
                             "max": 2816}
    assert mix["engine"] == {"max_slots": 128, "max_len": 2944,
                             "chunk_size": 16, "paged_kv": True,
                             "kv_page_len": 128, "prefill_chunk": 128,
                             "max_queue": 128}
    # the queue holds what the clients hand over at once (default: 64, and
    # the closed loop spins on a QueueFull it never drains)
    assert mix["engine"]["max_queue"] >= mix["clients"]
    assert "schedule_seed" in mix
    # the longest request fits a slot, and the shortest outlives the slots
    assert mix["prompt"]["max"] + mix["output"]["max"] <= \
        mix["engine"]["max_len"]
    assert mix["output"]["min"] / mix["engine"]["chunk_size"] >= \
        mix["engine"]["max_slots"]
    assert {m["name"] for m in cell.metrics("end_to_end")} == \
        {"serve_tok_s", "setup_s"}
    reports = {m["name"] for m in cell.metrics("per_layer")}
    assert set(READERS) | {
        "expert_time_pct", "router_time_pct", "shared_expert_time_pct",
        "decode.engine_step_ms", "decode.slot_occupancy_pct",
        "decode.kernel_time_pct", "decode.device_idle_pct",
        "decode.peak_hbm_gib", "decode.kv_move_time_pct",
        "decode.host_ms_step", "decode.step_move_time_pct"} == reports
    # their readers count a key and a value a head / intermediate_size and
    # every layer as holding experts
    assert not {"decode.decode_attn_roofline", "expert_held_roofline",
                "expert_ffn_roofline"} & reports
    layers = {m["name"]: m["layer"] for m in manifest["per_layer"]}
    assert layers["latent_attn_time_pct"] == \
        layers["latent_decode_roofline"] == "latent attention"
    assert layers["expert_share_roofline"] == layers["expert_time_pct"]


def test_the_builder_counts_the_cache_and_the_parameters(model):
    # the latent and the one rotary key in bf16: what must be READ
    assert model.kv_bytes_per_token_layer() == (512 + 64) * 2 == 1152
    assert (model.n_layer, model.n_head, model.head_dim, model.vocab_size) \
        == (6, 128, 192, 16160)
    cfg = model.module.config
    assert (cfg.n_experts, cfg.held, cfg.experts_per_token, cfg.n_group,
            cfg.topk_group, cfg.routed_scaling, cfg.router_scoring) == \
        (256, (0, 8), 8, 8, 4, 2.5, "sigmoid")
    assert (cfg.dense_layers, cfg.dense_width, cfg.expert_width,
            cfg.shared_width) == (1, 18432, 2048, 2048)
    assert cfg.rope_yarn == (40.0, 4096, 32.0, 1.0, 1.0, 1.0)
    assert abs(cfg.softmax_scale - 0.13523) < 1e-5
    sizes = model.sizes()
    attention = 187_105_280 + 1536 + 512 + 2 * 7168
    assert sizes["params"] == 6 * attention + 396_361_728 \
        + 5 * (7168 * 256 + 256 + 8 * 44_040_192 + 44_040_192) \
        + 2 * 16160 * 7168 + 7168              # 3,742M parameters: 7.48 GB
    assert round(sizes["params"] * 2 / 1e9, 2) == 7.48
    assert sizes["latent_stored_width"] == 640
    from deepspeed_tpu.models.decoder import cache_spec

    spec = cache_spec(cfg)
    assert (spec.n_layer, spec.n_head, spec.n_embd, spec.latent) == \
        (6, 1, 640, 512)


def test_the_builder_refuses_what_it_does_not_build(config):
    build = harness.load_by_name("model_builders", "deepseek_v3").Model
    for key, value in (("scoring_func", "softmax"),
                       ("topk_method", "greedy"),
                       ("num_nextn_predict_layers", 1),
                       ("n_routed_experts", 256),
                       ("num_key_value_heads", 8)):
        with pytest.raises(ValueError):
            build(dict(config[1], **{key: value}))


def _tiny_model(dtype=None):
    standin = tiny.standins()[CELL]
    body = harness.load_json(harness._find(
        harness.paths(), "configs", standin["config"] + ".json"))
    assert body["model_type"] == "deepseek_v3" and set(PUBLISHED) <= set(body)
    if dtype:
        body = dict(body, deployment=dict(body["deployment"],
                                          compute_dtype=dtype))
    builder = harness.load_by_name("model_builders", "deepseek_v3")
    model = builder.Model(body)
    return builder, model, model.init_params(7)


def _streams(model, rows=2, length=48):
    return np.random.RandomState(3).randint(
        0, model.vocab_size, size=(rows, length)).astype(np.int32)


def test_the_program_is_the_reference_at_the_stand_ins_size():
    """float32 at the tiny size: the cache-free pass (absorbed) against the
    plain reference (expanded) to 2e-4 on logits that spread 0.6 (the order
    of the sums). The seed reaches the selection bias and the head's scale."""
    builder, model, params = _tiny_model("float32")
    ids = _streams(model, length=24)
    want = builder.reference_logits(params, ids, model.cfg)
    got = model.module.apply({"params": params}, jnp.asarray(ids))
    assert want.dtype == np.float32 and want.std() > 0.4
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)
    bias = np.asarray(params["moe"]["router_bias"])
    assert bias.shape == (2, 16) and 0.03 < bias.std() < 0.3
    assert np.asarray(params["lm_head"], np.float32).std() == \
        pytest.approx(0.08, rel=0.1)


def test_latent_attention_sits_on_the_ridge():
    """2 x 128 x (576 + 512) FLOP for 1,152 bytes a cached token; with the
    queries and results of a row the bytes set the least time at a context
    of 1,200."""
    cost = costs_deepseek_v3.latent_decode_cost([1200] * 128, 128, 512, 64)
    tokens = 128 * 1200
    assert cost["flops"] == 278_528 * tokens
    assert cost["bytes"] == 1152 * tokens + 128 * 128 * 1088 * 2
    assert 278_528 / 1152.0 == pytest.approx(241.8, rel=1e-3)
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    seconds, bound = costs.least_seconds(cost["flops"], cost["bytes"], peaks)
    assert bound == "memory" and seconds == pytest.approx(0.2596e-3, rel=1e-3)
    assert cost["flops"] / 197e12 == pytest.approx(0.2172e-3, rel=1e-3)


@pytest.mark.parametrize("rows, touched", [
    (1, 0.25), (128, 8 * (1 - (248 / 256.0) ** 128)), (100000, 8.0)])
def test_held_experts_touched_under_uniform_routing(rows, touched):
    assert costs_granitemoehybrid.experts_touched(rows, 8, 256, 8) == \
        pytest.approx(touched)


def test_the_expert_share_is_bound_by_its_weights_at_decode_shapes():
    cost = costs_granitemoehybrid.expert_held_cost(128, 8, 256, 8, 7168,
                                                   2048)
    assert cost["experts_touched"] == pytest.approx(7.863, rel=1e-3)
    assert cost["bytes"] == pytest.approx(
        (7.863 * 44_040_192 + 2 * 128 * 7168) * 2, rel=1e-3)
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    seconds, bound = costs.least_seconds(cost["flops"], cost["bytes"], peaks)
    assert bound == "memory" and seconds == pytest.approx(0.850e-3, rel=1e-2)


def _readers():
    return {name: harness.load_by_name("layer_metrics", name)
            for name in READERS}


def _run(name, mixed, config, context=1200):
    run = _context(name, _hand_built(name, mixed), config)
    run["counters"].update(slots=128, trace_context=[[context] * 128],
                           trace_steps=1, chunk_size=1)
    return run


def test_the_three_readers_on_a_hand_built_trace(manifest):
    """``test_scope_reduce.py``'s trace with the family's names in it: the
    scan's kernel (2 calls, 4 us) is ``latent_decode``, its matmul fusion
    (3 us) sits under ``moe/experts``."""
    prefix = "jit(mixed_step)/decode_scan/while/body/closed_call/"
    mixed = dict(MIXED, **{
        "paged_decode.3": ("custom-call",
                           prefix + "attn/latent_decode/pallas_call"),
        "fusion.9": ("fusion", prefix + "moe/experts/dot_general")})
    config = harness.Cell(manifest, CELL).config
    run = _run("dsv3-hand-built", mixed, config)
    busy = run["trace"]["busy_s"]
    readers = _readers()
    # the scan's kernel and the lane's prefill_attn are both under attn
    assert readers["latent_attn_time_pct"].read(run) == \
        pytest.approx(100.0 * 7 * US / busy)
    cost = costs_deepseek_v3.latent_decode_cost([1200] * 128, 128, 512, 64)
    assert readers["latent_decode_roofline"].read(run) == \
        pytest.approx(100.0 * 2 * (cost["bytes"] / 819e9) / (4 * US))
    # 2 calls of 6 an iteration: a third of an iteration, 5 expert layers
    share = costs_granitemoehybrid.expert_held_cost(128, 8, 256, 8, 7168,
                                                    2048)
    assert readers["expert_share_roofline"].read(run) == \
        pytest.approx(100.0 * (2 / 6.0) * 5 * (share["bytes"] / 819e9)
                      / (3 * US))


def test_the_readers_return_nothing_for_a_program_without_the_kernel(
        manifest):
    """A parent commit's trace (it cannot run the cell, but the readers run
    on every trace), or another family's: nothing raises, nothing is
    reported."""
    config = harness.Cell(manifest, CELL).config
    prefix = "jit(mixed_step)/decode_scan/while/body/closed_call/"
    mixed = dict(MIXED, **{
        "fusion.9": ("fusion", prefix + "moe/experts/dot_general")})
    run = _run("dsv3-no-kernel", mixed, config)
    got = {n: r.read(run) for n, r in _readers().items()}
    assert got["latent_decode_roofline"] is None
    assert got["expert_share_roofline"] is None
    # another family's cell, whose configuration has no latent attention
    olmoe = harness.Cell(manifest, "serve-olmoe-decode-closed").config
    run = _run("dsv3-no-kernel", mixed, olmoe)
    assert {n: r.read(run) for n, r in _readers().items()} == \
        dict.fromkeys(READERS)


def test_the_names_file_brings_the_kernel_and_its_class():
    from benchmark import scope_reduce, trace_reduce

    names = scope_reduce.scope_names()
    assert "latent_decode" in names["kernels"]
    assert {"q_proj", "kv_proj", "absorb", "o_proj"} <= set(names["scopes"])
    classes = trace_reduce.kernel_names()["classes"]
    assert classes["latent_attn"] == [
        "(^|/)latent_decode[^/ ]* custom-call tpu_custom_call$"]
    # and not in decode_attn, whose roofline counts a key and a value
    import re

    label = "latent_decode.7 custom-call tpu_custom_call"
    assert not any(re.search(p, label) for p in classes["decode_attn"])
    assert re.search(classes["latent_attn"][0], label)


# ------------------------------------- what holds the stated precision


def _margin(logits, ids):
    """The serve driver's reading of a stream ``ids`` served whole."""
    picked = np.take_along_axis(logits[:, :-1], ids[:, 1:, None], axis=2)
    return float((logits[:, :-1].max(axis=2) - picked[..., 0]).max())


LIMITS = {"router_logit_err": "ROUTER_LIMIT", "latent_rel_err": "LATENT_LIMIT",
          "attention_rel_err": "ATTENTION_LIMIT"}


def test_the_sound_program_is_inside_its_three_precision_limits():
    """bf16 compute as the cell serves it: on the reference's own inputs the
    program's router is the reference's to float32 rounding, the latent it
    would cache and what its attention adds to the stream through a paged
    latent pool (three lane slices, then a decode step of a row a page) are
    the reference's to bf16 rounding, and the logits come back as the
    reference gives them but where its own routing is a near-tie."""
    builder, model, params = _tiny_model("bfloat16")
    ids = _streams(model, length=300)
    held = builder.Precision(params, model.cfg)
    want = builder.reference_logits(params, ids, model.cfg, watch=held.watch)
    readings = held.readings()
    assert len(held.latent) == 2 * 3 and len(held.router) == 2 * 2
    assert len(held.attention) == 2 * 3 * 2
    assert readings["router_logit_err"] < builder.ROUTER_LIMIT / 10
    assert 0 < readings["latent_rel_err"] < builder.LATENT_LIMIT * 0.6
    # hidden 64: a token's error spreads wider than at the cell's 7,168
    assert 0 < readings["attention_rel_err"] < 0.02
    ties = held.ties(ids.shape)
    assert ties.shape == ids.shape and 0 < ties.sum() < ties.size
    got = model.reference_logits(params, ids) if held.ok() else None
    if got is not None:
        np.testing.assert_array_equal(got[~ties], want[~ties])


def test_the_attention_probe_is_exact_in_float32():
    """The probe's own arithmetic (pages, table, frontiers, the rows of the
    decode step) adds nothing: in float32 the absorbed form through the paged
    pool is the reference's expanded attention to the order of the sums."""
    builder, model, params = _tiny_model("float32")
    ids = _streams(model, length=300)
    held = builder.Precision(params, model.cfg)
    builder.reference_logits(params, ids, model.cfg, watch=held.watch)
    assert max(held.attention) < 5e-6 and max(held.latent) < 5e-6


def _bf16_router(monkeypatch):
    from deepspeed_tpu.models import decoder

    monkeypatch.setattr(decoder, "router_logits", lambda n32, router: jnp.dot(
        n32.astype(jnp.bfloat16), router.astype(jnp.bfloat16)).astype(
            jnp.float32))
    return {"router_logit_err"}


def _fp8_latent(monkeypatch):
    from deepspeed_tpu.models import decoder

    real = decoder.latent_token
    monkeypatch.setattr(
        decoder, "latent_token", lambda *a: real(*a).astype(
            jnp.float8_e4m3fn).astype(jnp.bfloat16))
    # what a token caches is what attention reads
    return {"latent_rel_err", "attention_rel_err"}


def _fp8_page(monkeypatch):
    from deepspeed_tpu.models import generation

    real = generation.CacheAttention._latent
    monkeypatch.setattr(
        generation.CacheAttention, "_latent",
        lambda self, i, q, k, planes: real(self, i, q, k.astype(
            jnp.float8_e4m3fn).astype(k.dtype), planes))
    return {"attention_rel_err"}


def _unscaled_softmax(monkeypatch):
    from deepspeed_tpu.models import decoder

    monkeypatch.setattr(decoder.DecoderConfig, "softmax_scale", property(
        lambda self: float(self.qk_nope_dim + self.qk_rope_dim) ** -0.5))
    return {"attention_rel_err"}


@pytest.mark.parametrize("lower", [_bf16_router, _fp8_latent, _fp8_page,
                                   _unscaled_softmax])
def test_the_precision_below_the_stated_one_is_not_correct(monkeypatch,
                                                           lower):
    """The router's matmul in bf16, the cached latent rounded to 8 bits, the
    page rounded to 8 bits as it is written, a softmax without YaRN's
    temperature: the comparison that holds it reads over its limit, the
    others stay inside theirs, and no token of the logits handed to the
    driver is within its margin."""
    builder, model, params = _tiny_model("bfloat16")
    monkeypatch.setattr(builder, "ATTENTION_LIMIT", 0.02)     # hidden 64
    ids = _streams(model, length=300)
    over = lower(monkeypatch)
    builder._mix.cache_clear()
    held = builder.Precision(params, model.cfg)
    builder.reference_logits(params, ids, model.cfg, watch=held.watch)
    readings = held.readings()
    for name, limit in LIMITS.items():
        if name in over:
            assert readings[name] > 2 * getattr(builder, limit), name
        else:
            assert readings[name] < getattr(builder, limit), name
    assert not held.ok()
    got = model.reference_logits(params, ids)
    builder._mix.cache_clear()
    assert _margin(got, ids) > builder.REFUSED / 2


def _scores(builder, rows):
    """Router logits [len(rows), 16] whose sigmoid is what ``rows`` say
    (expert -> score; 0.05 elsewhere), for a router of 4 groups of 4 that
    keeps 2 groups and 3 experts, experts 0-7 (groups 0 and 1) held."""
    s = np.full((len(rows), 16), 0.05)
    for t, row in enumerate(rows):
        for e, v in row.items():
            s[t, e] = v
    return np.log(s / (1 - s)).astype(np.float32)


def test_a_near_tie_is_read_off_the_references_own_scores():
    """``tie_distance``: a held expert's gap to the edge of the choice, kept
    or left out, over the noise times the root of the two sigmoid slopes'
    squares; a kept and a cut group changing sides where that keeps another
    set of held experts; a tie among experts or groups held elsewhere that
    leaves the held ones where they are is none."""
    builder, model, _ = _tiny_model("float32")
    cfg = model.cfg
    assert (cfg.n_experts, cfg.n_group, cfg.topk_group,
            cfg.experts_per_token, cfg.held) == (16, 4, 2, 3, (0, 8))
    rows = [
        # groups 0 and 2 kept by far; held 0, 1 and absent 8 chosen by far
        {0: 0.9, 1: 0.8, 8: 0.85, 9: 0.5, 4: 0.3, 12: 0.3},
        # held 1 kept, 0.01 over absent 9: the edge of the choice
        {0: 0.9, 1: 0.8, 8: 0.85, 9: 0.79, 4: 0.3, 12: 0.3},
        # held 1 left out, 0.01 under absent 9
        {0: 0.9, 1: 0.78, 8: 0.85, 9: 0.79, 4: 0.3, 12: 0.3},
        # absent 8 and 9 tie for the last place: no held expert near it
        {0: 0.9, 1: 0.88, 8: 0.6, 9: 0.6, 4: 0.3, 12: 0.3},
        # group 1 (held 4, 5) 0.02 under group 2 for the second place, and
        # kept it would bring held 4 into the choice
        {0: 0.9, 1: 0.8, 4: 0.7, 5: 0.63, 8: 0.75, 9: 0.6, 12: 0.3},
        # groups 2 and 3 (nothing held) tie for the second place
        {0: 0.9, 1: 0.8, 2: 0.7, 8: 0.5, 9: 0.4, 12: 0.5, 13: 0.4},
    ]

    def slope(*scores):
        return np.sqrt(sum((v * (1 - v)) ** 2 for v in scores))

    far = builder.tie_distance(_scores(builder, rows), np.zeros(16), cfg,
                               noise=0.05)
    want = [0.3 / slope(0.8, 0.5), 0.01 / slope(0.8, 0.79),
            0.01 / slope(0.78, 0.79), 0.28 / slope(0.88, 0.6),
            0.02 / slope(0.75, 0.6, 0.7, 0.63), 0.2 / slope(0.7, 0.5)]
    np.testing.assert_allclose(far, np.asarray(want) / 0.05, rtol=1e-4)
    assert builder.near_tie(_scores(builder, rows), np.zeros(16), cfg, 0.05,
                            sigmas=3.5).tolist() == [
        False, True, True, False, True, False]
    # the bias moves the scores that CHOOSE (not the slopes): row 0 a tie
    bias = np.zeros(16)
    bias[9] = 0.28
    np.testing.assert_allclose(
        builder.tie_distance(_scores(builder, rows[:1]), bias, cfg, 0.05),
        [0.02 / slope(0.8, 0.5) / 0.05], rtol=1e-4)


def test_an_exempt_position_reads_no_margin_and_no_other_moves():
    builder = harness.load_by_name("model_builders", "deepseek_v3")
    rng = np.random.RandomState(0)
    logits = rng.randn(2, 9, 11).astype(np.float32)
    ids = rng.randint(0, 11, size=(2, 9))
    ties = rng.rand(2, 9) < 0.3
    out = builder.exempted(logits.copy(), ids, ties)
    margin = out[:, :-1].max(axis=2) - np.take_along_axis(
        out[:, :-1], ids[:, 1:, None], axis=2)[..., 0]
    assert (margin[ties[:, :-1]] == 0).all()
    np.testing.assert_array_equal(out[~ties], logits[~ties])
    assert (out != logits).sum() <= ties.sum()


def test_refused_logits_put_every_position_outside_the_margin():
    builder = harness.load_by_name("model_builders", "deepseek_v3")
    rng = np.random.RandomState(0)
    logits = rng.randn(2, 9, 11).astype(np.float32)
    ids = rng.randint(0, 11, size=(2, 9))
    out = builder.refused(logits.copy(), ids)
    picked = np.take_along_axis(out[:, :-1], ids[:, 1:, None], axis=2)[..., 0]
    assert ((out[:, :-1].max(axis=2) - picked) > builder.REFUSED - 10).all()
    assert (out != logits).sum() == 2 * 9


def test_the_reference_gets_the_checkpoints_layout_back():
    """The program keeps the rotary columns in halves order and ``kv_b_proj``
    as two stacks; the builder hands the reference the published layout:
    interleaved pairs, one ``[rank, heads x (nope + v)]`` matrix."""
    builder, model, params = _tiny_model("float32")
    np.testing.assert_array_equal(builder.interleaved(8),
                                  [0, 4, 1, 5, 2, 6, 3, 7])
    cfg = model.cfg
    layer = next(iter(builder.published_names(params, cfg)["layers"]))
    a = {k: np.asarray(v[0]) for k, v in params["mla"].items()}
    dn, dr, dv, r = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim, \
        cfg.kv_lora_rank
    q_b = np.asarray(layer["q_b_proj"]).reshape(-1, cfg.n_head, dn + dr)
    nope = a["wq_nope"].T.reshape(-1, cfg.n_head, dn)
    rope = a["wq_rope"].T.reshape(-1, cfg.n_head, dr)
    np.testing.assert_array_equal(q_b[..., :dn], nope)
    np.testing.assert_array_equal(q_b[..., dn::2], rope[..., :dr // 2])
    np.testing.assert_array_equal(q_b[..., dn + 1::2], rope[..., dr // 2:])
    kv_a = np.asarray(layer["kv_a_proj_with_mqa"])
    np.testing.assert_array_equal(kv_a[:, r::2], a["wkv_a"][:, r:r + dr // 2])
    kv_b = np.asarray(layer["kv_b_proj"]).reshape(r, cfg.n_head, dn + dv)
    np.testing.assert_array_equal(kv_b[..., :dn],
                                  a["w_uk"].transpose(1, 0, 2))
    np.testing.assert_array_equal(kv_b[..., dn:], a["w_uv"].transpose(2, 0, 1))
