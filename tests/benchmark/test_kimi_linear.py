"""The Kimi Linear family's own benchmark files: its configuration against
the published keys and its own arithmetic, its cell's traffic, its builder
against the reference at the stand-in's size, its cost by hand, its two
readers on a hand-built trace and on one that lacks the family's regions (a
parent commit's), and the comparisons that hold the stated precision."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import costs_kimi_linear, harness
from tests.benchmark import tiny
from tests.benchmark.test_deepseek_v3 import _bf16_router, _fp8_latent, \
    _fp8_page
from tests.benchmark.test_olmoe import _context, _hand_built
from tests.benchmark.test_scope_reduce import MIXED, US

CELL = "serve-kimilinear-decode-closed"
CONFIG = "kimi-linear-48b-a3b-12l-ep16"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
READERS = ("kda_time_pct", "kda_update_roofline")
PERIODS = {"full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
           "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                          21, 22, 23, 25, 26],
           "num_heads": 32, "short_conv_kernel_size": 4}
# The language model's settings as its public config.json gives them.
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": PERIODS, "mla_use_nope": True,
    "model_max_length": 1048576, "model_type": "kimi_linear",
    "moe_intermediate_size": 1024, "moe_layer_freq": 1,
    "moe_renormalize": True, "moe_router_activation_func": "sigmoid",
    "num_attention_heads": 32, "num_expert_group": 1, "num_experts": 256,
    "num_experts_per_token": 8, "num_hidden_layers": 27,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 0,
    "num_shared_experts": 1, "q_lora_rank": None, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "routed_scaling_factor": 2.446,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_topk": True,
    "v_head_dim": 128, "vocab_size": 163840}
CUT = {"num_hidden_layers", "linear_attn_config", "num_experts", "vocab_size"}


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


@pytest.fixture(scope="module")
def builder():
    return harness.load_by_name("model_builders", "kimi_linear")


def test_every_published_key_is_in_the_configuration_unchanged(config):
    entry, body = config
    differs = {k for k, v in PUBLISHED.items() if body.get(k, "absent") != v}
    assert differs == set(body["reduced"]) == set(entry["reduced"]) == CUT
    assert entry["source"] == body["source"]
    # three whole periods, 9 KDA : 3 MLA = the published 3 : 1; the sizes of
    # the group (a head's size among them) as published
    linear = body["linear_attn_config"]
    assert linear["kda_layers"] == [1, 2, 3, 5, 6, 7, 9, 10, 11]
    assert linear["full_attn_layers"] == [4, 8, 12]
    assert {k: v for k, v in linear.items() if not k.endswith("_layers")} \
        == {k: v for k, v in PERIODS.items() if not k.endswith("_layers")}
    assert body["num_hidden_layers"] == 12 and \
        body["first_k_dense_replace"] == 1
    # the experts held, with the published count and the router's width
    assert body["num_experts"] == 16 == body["experts_held"][1]
    assert body["router_outputs"] == 256 == body["published"]["num_experts"]
    assert body["vocab_size"] * 8 == body["published"]["vocab_size"]
    assert body["published"] == {k: PUBLISHED[k] for k in CUT}
    # no width is cut, and none may ever be listed as cut
    assert not any(k.endswith(("_size", "_dim", "_rank", "_head"))
                   or k == "num_experts_per_token"
                   for k in CUT - {"vocab_size"})
    assert body["deployment"]["chips"] == 1
    assert body["deployment"]["stands_for_chips"] == 16
    for said in ("conv_bias", "g_b_proj_bias", "gate_rank", "l2norm_eps",
                 "A_log_dt_bias", "nope", "state_dtype", "router",
                 "router_bias_init_range", "lm_head_init_range", "near_ties",
                 "latent_cache", "weights"):
        assert body["assumed"][said]
    assert body["lm_head_init_range"] == pytest.approx(0.645 / 2304 ** 0.5,
                                                       abs=1e-5)
    for said in ("39.51M", "29.11M", "49.1B", "3.86 GB", "2.50 GB",
                 "1.51 GB", "7.87 GB"):
        assert said in body["reduced_why"], said


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_configuration_holds_every_number_of_the_catalog_row(config):
    import json

    row, = [r for r in map(json.loads, open(CATALOG))
            if r["name"] == "Kimi-Linear-48B-A3B-Instruct"]
    _, body = config
    assert row["source_url"] == body["source"]
    assert row["config"] == PUBLISHED
    for key, value in row["config"].items():
        if key not in body["reduced"]:
            assert body[key] == value, key


def test_the_cell_is_one_chip_with_dsv3s_traffic_unchanged(manifest):
    cell = harness.Cell(manifest, CELL)
    assert cell.chips == 1 and cell.traffic_name == "latent-decode-closed"
    assert cell.traffic == harness.Cell(
        manifest, "serve-dsv3-decode-closed").traffic
    assert {m["name"] for m in cell.metrics("end_to_end")} == \
        {"serve_tok_s", "setup_s"}
    reports = {m["name"] for m in cell.metrics("per_layer")}
    assert set(READERS) | {
        "expert_time_pct", "router_time_pct", "shared_expert_time_pct",
        "latent_attn_time_pct", "latent_decode_roofline",
        "decode.engine_step_ms", "decode.slot_occupancy_pct",
        "decode.kernel_time_pct", "decode.device_idle_pct",
        "decode.peak_hbm_gib", "decode.kv_move_time_pct",
        "decode.host_ms_step", "decode.step_move_time_pct"} == reports
    # its reader reads DeepSeek's key names and counts every layer as an
    # expert layer's iteration (ROADMAP.md Reach B1g)
    assert "expert_share_roofline" not in reports
    layers = {m["name"]: m for m in manifest["per_layer"]}
    for name in READERS:
        assert layers[name]["layer"] == "linear-attention mixer"
        assert layers[name]["workloads"] == [CELL]
        assert layers[name]["moves"] == "serve_tok_s"
    # the only cells on four chips stay the ones that were
    assert [w["name"] for w in manifest["workloads"] if w["chips"] == 4] == \
        ["train-gpt2xl-zero-dp4"]


def test_the_builder_counts_the_caches_and_the_parameters(model):
    """The file's arithmetic against the tree the builder makes."""
    from deepspeed_tpu.inference.kv_pool import slot_state_nbytes
    from deepspeed_tpu.models.decoder import cache_spec

    # the latent and the one shared key in bf16: what must be READ
    assert model.kv_bytes_per_token_layer() == (512 + 64) * 2 == 1152
    spec = cache_spec(model.cfg)
    # what the pool STORES a token over the layers that cache: 3 x 640 x 2
    assert (spec.n_layer, spec.n_head, spec.n_embd, spec.latent) == \
        (3, 1, 640, 512)
    assert spec.n_layer * spec.n_embd * 2 == 3 * 1280
    assert slot_state_nbytes(spec) == 9 * (32 * 128 * 128 * 4
                                           + 3 * 12288 * 2) == 19537920
    sizes = model.sizes()
    assert sizes["state_bytes_per_slot"] == 19537920
    assert sizes["latent_layers"] == 3
    tree = jax.eval_shape(lambda: model.module.init(
        jax.random.PRNGKey(0))["params"])
    made = sum(int(np.prod(a.shape))
               for a in jax.tree_util.tree_leaves(tree))
    assert sizes["params"] == made
    # 3.86 GB of weights within 1%
    assert abs(made * 2 / 3.86e9 - 1) < 0.01
    assert all(a.dtype == jnp.bfloat16 or a.ndim <= 2
               for a in jax.tree_util.tree_leaves(tree))
    assert (model.n_layer, model.n_head, model.head_dim,
            model.vocab_size) == (12, 32, 192, 20480)
    assert model.cfg.kinds == ("kda", "kda", "kda", "attention") * 3
    assert model.cfg.held == (0, 16) and model.cfg.n_experts == 256
    assert not model.cfg.rope and model.cfg.q_lora_rank == 0
    assert model.cfg.softmax_scale == pytest.approx(192 ** -0.5)


def test_the_builder_refuses_what_it_does_not_build(config, builder):
    _, body = config
    for key, other in (("mla_use_nope", False), ("q_lora_rank", 1536),
                       ("num_expert_group", 8), ("moe_renormalize", False),
                       ("moe_router_activation_func", "softmax")):
        with pytest.raises(ValueError, match=key):
            builder.Model(dict(body, **{key: other}))
    with pytest.raises(ValueError, match="each of the 12 layers once"):
        builder.Model(dict(body, linear_attn_config=dict(
            body["linear_attn_config"], full_attn_layers=[4, 8])))
    with pytest.raises(ValueError, match="counts the experts held"):
        builder.Model(dict(body, num_experts=8))


def test_the_program_is_the_reference_at_the_stand_ins_size(builder):
    m = tiny.manifest()
    model = harness.load_model(harness.Cell(m, "serve-tiny-kimilinear"))
    params = model.init_params(3)
    ids = np.random.RandomState(0).randint(0, model.vocab_size, (2, 24))
    want = builder.reference_logits(params, ids, model.cfg)
    got = np.asarray(jax.jit(model.module.apply)({"params": params},
                                                 jnp.asarray(ids)))
    assert 0.3 < want.std(axis=-1).mean() < 1.0
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    # the same seed gives the same weights; seeds pass 2**31
    again = model.init_params(3)
    assert all(bool((a == b).all()) for a, b in zip(
        jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(again)))
    assert model.init_params(2 ** 31 + 5)["lm_head"].shape == (64, 256)


def test_the_update_moves_the_state_once_each_way():
    """128 slots of 32 heads of [128, 128] float32: 268 MB a layer read and
    written once, the vectors beside it kilobytes; 0.66 ms at 819 GB/s."""
    nbytes = costs_kimi_linear.kda_update_bytes(128, 32, 128, 128)
    state = 128 * 32 * 128 * 128 * 4
    assert state == 268435456
    assert nbytes == 2 * state + 128 * 32 * (3 * 128 + 2 * 128 + 1) * 4
    assert nbytes / (2 * state) < 1.02
    assert 0.65e-3 < nbytes / 819e9 < 0.67e-3
    # nine layers an iteration: the issue's 4.83 GB of state
    assert 4.83e9 < 9 * 2 * state < 4.84e9


def _readers():
    return {name: harness.load_by_name("layer_metrics", name)
            for name in READERS}


def _run(name, mixed, config):
    run = _context(name, _hand_built(name, mixed), config)
    run["counters"].update(slots=128, trace_steps=1, chunk_size=1)
    return run


def test_the_two_readers_on_a_hand_built_trace(manifest):
    """``test_scope_reduce.py``'s trace with the family's names in it: the
    scan's kernel (2 calls, 4 us) is ``latent_decode``, its matmul fusion
    (3 us) sits under ``kda/update``, its movement fusion (3 us) under
    ``kda/conv``."""
    prefix = "jit(mixed_step)/decode_scan/while/body/closed_call/"
    mixed = dict(MIXED, **{
        "paged_decode.3": ("custom-call",
                           prefix + "attn/latent_decode/pallas_call"),
        "fusion.9": ("fusion", prefix + "kda/update/reduce_sum"),
        "slice_bitcast_fusion.2": ("fusion", prefix + "kda/conv/add")})
    config = harness.Cell(manifest, CELL).config
    run = _run("kimi-hand-built", mixed, config)
    readers = _readers()
    assert readers["kda_time_pct"].read(run) == \
        pytest.approx(100.0 * 6 * US / run["trace"]["busy_s"])
    # 2 calls of 3 an iteration: two thirds of an iteration, 9 KDA layers
    nbytes = costs_kimi_linear.kda_update_bytes(128, 32, 128, 128)
    assert readers["kda_update_roofline"].read(run) == \
        pytest.approx(100.0 * (2 / 3.0) * 9 * (nbytes / 819e9) / (3 * US))


def test_the_readers_return_nothing_for_a_program_without_the_regions(
        manifest):
    """A parent commit's trace (it cannot run the cell, but the readers run
    on every trace), or another family's: nothing raises, nothing is
    reported."""
    config = harness.Cell(manifest, CELL).config
    prefix = "jit(mixed_step)/decode_scan/while/body/closed_call/"
    mixed = dict(MIXED, **{
        "paged_decode.3": ("custom-call",
                           prefix + "attn/latent_decode/pallas_call")})
    run = _run("kimi-no-region", mixed, config)
    assert {n: r.read(run) for n, r in _readers().items()} == \
        dict.fromkeys(READERS)
    # the region without the kernel that counts the iterations
    mixed = dict(MIXED, **{
        "fusion.9": ("fusion", prefix + "kda/update/reduce_sum")})
    run = _run("kimi-no-kernel", mixed, config)
    assert _readers()["kda_update_roofline"].read(run) is None
    # another family's cell, whose configuration has no KDA layer
    dsv3 = harness.Cell(manifest, "serve-dsv3-decode-closed").config
    mixed = dict(mixed, **{
        "paged_decode.3": ("custom-call",
                           prefix + "attn/latent_decode/pallas_call")})
    run = _run("kimi-other-family", mixed, dsv3)
    assert _readers()["kda_update_roofline"].read(run) is None


def test_the_names_file_brings_the_regions_and_no_kernel():
    from benchmark import scope_reduce

    names = scope_reduce.scope_names()
    assert {"kda", "qkv_proj", "conv", "gate", "update", "gate_norm",
            "o_proj"} <= set(names["scopes"])
    family = harness.load_json(os.path.join(
        harness.ROOT, "benchmark", "names", "kimi_linear.json"))
    assert "kernels" not in family and "classes" not in family


# ------------------------------------- what holds the stated precision


@pytest.fixture(scope="module")
def probed(builder):
    """The stand-in in bf16, as the cell serves it, with what the
    reference shows of one KDA layer and one MLA layer."""
    m = tiny.manifest()
    cell = harness.Cell(m, "serve-tiny-kimilinear")
    cell.config = dict(cell.config, deployment=dict(
        cell.config["deployment"], compute_dtype="bfloat16"))
    model = builder.Model(cell.config)
    params = model.init_params(5)
    ids = np.random.RandomState(1).randint(0, model.vocab_size, (1, 48))
    seen = {}
    builder.reference_logits(params, ids, model.cfg, watch=lambda layer, b, s:
                             seen.setdefault(layer, s))
    return model, params, ids, seen


def test_the_sound_program_is_inside_its_precision_limits(builder, probed):
    model, params, ids, _ = probed
    held = builder.Precision(params, model.cfg)
    builder.reference_logits(params, ids, model.cfg, watch=held.watch)
    r = held.readings()
    assert held.ok(), r
    # float32 arithmetic on float32 inputs: rounding alone
    assert r["state_lane_rel_err"] < 1e-5 and r["state_rel_err"] < 1e-5
    assert r["router_logit_err"] < 1e-5
    # bf16 values from bf16 matmuls, under limits set at the cell's widths
    assert 1e-4 < r["tail_rel_err"] < builder.TAIL_LIMIT
    assert 1e-4 < r["latent_rel_err"] < builder.LATENT_LIMIT


def test_a_bf16_state_fails_the_state_probe(builder, probed):
    model, _, _, seen = probed
    sound = builder.state_errors(model.cfg, seen[0])
    lower = builder.state_errors(model.cfg, seen[0], dtype=jnp.bfloat16)
    assert max(sound) < 1e-5
    # rounded to 8 bits of mantissa once after the lane (2 ** -9 an element
    # at most: under the limit) and again after every token of the scan
    assert lower[0] > 100 * sound[0] and lower[1] > builder.STATE_LIMIT


def test_an_fp8_tail_fails_the_tail_probe(builder, probed):
    model, params, _, seen = probed
    got = builder.program_tail(params["kda"]["wqkv"][0], model.cfg,
                               seen[0]["mix_in"], dtype=jnp.float8_e4m3fn)
    assert builder.shared.latent_error(got, seen[0]["tail"]) > \
        builder.TAIL_LIMIT


@pytest.mark.parametrize("lower", [_bf16_router, _fp8_latent, _fp8_page])
def test_the_precision_below_fails_the_probes_it_shares_with_dsv3(
        builder, probed, monkeypatch, lower):
    """The router's matmul in bf16, the cached latent rounded to 8 bits, the
    page rounded to 8 bits as it is written: the comparison that holds it
    reads over its limit and the others stay inside theirs."""
    model, params, ids, _ = probed
    over = lower(monkeypatch)
    builder._mix.cache_clear()
    held = builder.Precision(params, model.cfg)
    builder.reference_logits(params, ids, model.cfg, watch=held.watch)
    builder._mix.cache_clear()
    limits = dict(builder.Precision.LIMITS)
    for name, reading in held.readings().items():
        assert (reading > limits[name]) == (name in over), (name, reading)
    assert not held.ok()


def test_a_failed_probe_puts_every_position_outside_the_margin(
        builder, probed, monkeypatch):
    model, params, ids, _ = probed
    monkeypatch.setattr(builder.Precision, "LIMITS", tuple(
        (name, 0.0 if name.startswith("state") else limit)
        for name, limit in builder.Precision.LIMITS))
    out = model.reference_logits(params, jnp.asarray(ids))
    picked = np.take_along_axis(out[:, :-1], ids[:, 1:, None], axis=2)[..., 0]
    assert float((out[:, :-1].max(axis=2) - picked).min()) > 100.0
