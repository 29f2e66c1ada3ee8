"""The LFM2 family's own benchmark files: its configuration against the
published keys and its own arithmetic (4,237M held, 8.34B whole), its cell's
traffic, its builder against the reference at the stand-in's size (whole
sequences, a prompt chunked at 1, 2, 3 and 128), its costs by hand, its three
readers on a hand-built trace and on one that lacks the family's regions (a
parent commit's), the at-once failure on a program without the kind, and the
comparisons that hold the stated precision."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import costs_lfm2_moe, harness, probe_lfm2_moe
from tests.benchmark import tiny
from tests.benchmark.test_olmoe import _context, _hand_built
from tests.benchmark.test_scope_reduce import MIXED, US

CELL = "serve-lfm2moe-decode-closed"
CONFIG = "lfm2-8b-a1b-12l"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
READERS = ("shortconv_time_pct", "expert_stream_roofline",
           "paged_decode_roofline")
PERIODS = ["conv", "conv", "full_attention", "conv"] * 5 \
    + ["conv", "full_attention", "conv", "conv"]
# The language model's settings as its public config.json gives them.
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168, "layer_types": PERIODS,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 24,
    "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}
CUT = {"num_hidden_layers", "layer_types", "num_dense_layers"}


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
    return harness.load_by_name("model_builders", "lfm2_moe")


def test_every_published_key_is_in_the_configuration_unchanged(config):
    entry, body = config
    differs = {k for k, v in PUBLISHED.items() if body.get(k, "absent") != v}
    assert differs == set(body["reduced"]) == set(entry["reduced"]) == CUT
    assert entry["source"] == body["source"]
    # the cut is depth alone: three whole periods, 9 conv : 3 attention =
    # the published 18 : 6, the leading dense layers counted once
    assert body["layer_types"] == PERIODS[:12] == \
        ["conv", "conv", "full_attention", "conv"] * 3
    assert PERIODS.count("conv") == 18 and PERIODS.count(
        "full_attention") == 6
    assert body["num_hidden_layers"] == 12 and body["num_dense_layers"] == 1
    assert body["published"] == {k: PUBLISHED[k] for k in CUT}
    # every expert and every token id is held: no share of a layer
    assert body["num_experts"] == 32 and body["vocab_size"] == 65536
    assert "experts_held" not in body and "vocab_held" not in body
    # no width is cut, and none may ever be listed as cut
    assert not any(k.endswith(("_size", "_dim", "_rank", "_head"))
                   or k == "num_experts_per_tok" for k in CUT)
    assert body["deployment"]["chips"] == 1
    assert body["deployment"]["stands_for_chips"] == 2
    assert "two pipeline stages" in body["deployment"]["layout"]
    for said in ("head_dim", "tie_word_embeddings", "conv_operator",
                 "qk_norm", "rope", "router", "router_norm_epsilon", "norms",
                 "state_dtype", "router_bias_init_range", "embed_init_range",
                 "final_norm_init", "near_ties", "kv_cache", "weights"):
        assert body["assumed"][said]
    assert body["final_norm_init"] * body["embed_init_range"] \
        * 2048 ** 0.5 == pytest.approx(0.645, abs=1e-3)
    for said in ("16.78M", "10.49M", "352.3M", "44.04M", "134.2M", "4,237M",
                 "8.47 GB", "8.34B", "2.32 GB", "9.4 MB", "10.8 GB"):
        assert said in body["reduced_why"], said


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_configuration_holds_every_number_of_the_catalog_row(config):
    import json

    row, = [r for r in map(json.loads, open(CATALOG))
            if r["name"] == "LFM2-8B-A1B"]
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
        "expert_time_pct", "router_time_pct", "decode.engine_step_ms",
        "decode.slot_occupancy_pct", "decode.kernel_time_pct",
        "decode.device_idle_pct", "decode.peak_hbm_gib",
        "decode.kv_move_time_pct", "decode.host_ms_step",
        "decode.step_move_time_pct"} == reports
    # it counts every layer as holding keys (three of twelve do)
    assert "decode.decode_attn_roofline" not in reports
    layers = {m["name"]: m for m in manifest["per_layer"]}
    assert [layers[name]["layer"] for name in READERS] == \
        ["short-convolution mixer", "expert feed-forward", "kernels"]
    for name in READERS:
        assert layers[name]["workloads"] == [CELL]
        assert layers[name]["moves"] == "serve_tok_s"
        assert layers[name]["unit"] == "%"
    # the new entries stand last in their lists
    assert manifest["configs"][-1]["name"] == CONFIG
    assert manifest["workloads"][-1]["name"] == CELL
    assert [m["name"] for m in manifest["per_layer"][-3:]] == list(READERS)
    # the only cell on four chips stays the one that was
    assert [w["name"] for w in manifest["workloads"] if w["chips"] == 4] == \
        ["train-gpt2xl-zero-dp4"]


def test_the_builder_counts_the_cut_and_the_whole_model(model, builder,
                                                        config):
    """The file's arithmetic against the tree the builder makes: 4,237M
    parameters held (8.47 GB) and, uncut, 8.34B."""
    from deepspeed_tpu.inference.kv_pool import slot_state_nbytes
    from deepspeed_tpu.models.decoder import cache_spec

    # a key and a value for 8 stored heads of 64 in bf16: 2,048 B a layer
    assert model.kv_bytes_per_token_layer() == 2 * 8 * 64 * 2 == 2048
    spec = cache_spec(model.cfg)
    assert (spec.n_layer, spec.n_head, spec.n_embd, spec.latent) == \
        (3, 8, 512, 0)
    # 3 layers x 128 slots x 2,944 positions x 2,048 B = 2.32 GB of keys
    assert 2.31e9 < 3 * 128 * 2944 * model.kv_bytes_per_token_layer() < 2.32e9
    assert slot_state_nbytes(spec) == 9 * 2 * 2048 * 2 == 73728
    sizes = model.sizes()
    assert sizes["state_bytes_per_slot"] == 73728 and sizes["kv_layers"] == 3
    tree = jax.eval_shape(lambda: model.module.init(
        jax.random.PRNGKey(0))["params"])
    made = sum(int(np.prod(a.shape))
               for a in jax.tree_util.tree_leaves(tree))
    assert sizes["params"] == made == 4237075168
    assert round(made / 1e6) == 4237 and abs(made * 2 / 8.47e9 - 1) < 1e-3
    assert all(a.dtype == jnp.bfloat16 or a.ndim <= 2
               for a in jax.tree_util.tree_leaves(tree))
    # by kind, as the file's arithmetic has them
    conv = sum(int(np.prod(a.shape[1:])) for a in tree["shortconv"].values())
    attn = sum(int(np.prod(a.shape[1:])) for a in tree["attn"].values())
    moe = sum(int(np.prod(a.shape[1:])) for a in tree["moe"].values())
    assert (conv, attn) == (16783360, 10485888)
    assert moe == 32 * 3 * 2048 * 1792 + 2048 * 32 + 32
    # the uncut configuration: 24 layers, 2 dense
    whole = builder.Model(dict(config[1], **config[1]["published"]))
    assert whole.cfg.n_layer == 24 and whole.cfg.dense_layers == 2
    assert len(whole.cfg.shortconv_layers) == 18
    assert round(whole.sizes()["params"] / 1e7) == 834
    assert (model.n_layer, model.n_head, model.head_dim,
            model.vocab_size) == (12, 32, 64, 65536)
    assert model.cfg.kinds == ("shortconv", "shortconv", "attention",
                               "shortconv") * 3
    assert model.cfg.held == (0, 32) and model.cfg.n_kv == 8
    assert model.cfg.qk_norm == "head" and model.cfg.rope
    assert model.cfg.tie_word_embeddings and model.cfg.shortconv_kernel == 3


def test_the_builder_refuses_what_it_does_not_build(config, builder):
    _, body = config
    for key, other in (("conv_bias", True), ("norm_topk_prob", False),
                       ("use_expert_bias", False)):
        with pytest.raises(ValueError, match=key):
            builder.Model(dict(body, **{key: other}))
    with pytest.raises(ValueError, match="each of the 12 layers"):
        builder.Model(dict(body, layer_types=body["layer_types"][:8]))
    with pytest.raises(ValueError, match="each of the 12 layers"):
        builder.Model(dict(body, layer_types=["mamba"] * 12))


def test_a_program_without_the_kind_fails_at_once(config, builder,
                                                  monkeypatch):
    """The parent commit under this PR's benchmark files: the builder says
    what the program lacks before any weight or engine exists."""
    from deepspeed_tpu.models import decoder

    monkeypatch.setattr(decoder, "RECURRENT", {
        k: v for k, v in decoder.RECURRENT.items() if k != "shortconv"})
    with pytest.raises(RuntimeError, match="no gated short convolution"):
        builder.Model(config[1])


def _tiny(builder, dtype=None):
    cell = harness.Cell(tiny.manifest(), "serve-tiny-lfm2moe")
    if dtype:
        cell.config = dict(cell.config, deployment=dict(
            cell.config["deployment"], compute_dtype=dtype))
    return builder.Model(cell.config)


def test_the_program_is_the_reference_at_the_stand_ins_size(builder):
    model = _tiny(builder)
    # all three kinds of layer, g = 2 x rep = 4, a dense leading layer
    assert set(model.cfg.kinds) == {"shortconv", "attention"}
    assert (model.cfg.n_head, model.cfg.n_kv, model.cfg.head_dim,
            model.cfg.dense_layers) == (8, 2, 64, 1)
    params = model.init_params(3)
    assert float(jnp.abs(params["moe"]["router_bias"]).max()) > 0.01
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
    assert model.init_params(2 ** 31 + 5)["embed"].shape == (256, 512)


@pytest.mark.parametrize("chunk", [1, 2, 3, 128])
def test_a_prompt_chunked_gives_the_references_logits_and_the_same_tails(
        builder, chunk):
    """29 tokens through the adapter's lane call in slices of ``chunk`` (pad
    columns beside the real ones where a slice is wider than what is left),
    each onto the tails and keys the slices before it left: the reference's
    logits at every position, and the tails of a prompt served whole."""
    from deepspeed_tpu.inference.adapters import DecoderAdapter

    model = _tiny(builder)
    params = model.init_params(3)
    adapter = DecoderAdapter.from_model(model.module, use_flash_decode=False)
    ids = np.random.RandomState(1).randint(0, model.vocab_size, (1, 29))
    want = builder.reference_logits(params, ids, model.cfg)[0]

    def served(chunk):
        cache, out = adapter.init_cache(1, 160), []
        for lo in range(0, 29, chunk):
            n = min(chunk, 29 - lo)
            piece = np.zeros((1, chunk), np.int32)
            piece[0, :n] = ids[0, lo:lo + n]
            logits, cache = adapter.prefill_append(
                params, jnp.asarray(piece), cache,
                n_valid=jnp.asarray([n], jnp.int32))
            out.append(logits[0, :n])
        return np.concatenate(out), cache

    got, cache = served(chunk)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    _, whole = served(29)
    assert int(cache["pos"][0]) == 29
    for j in range(3):
        name = "slot_shortconv{}".format(j)
        assert float(jnp.abs(whole[name]).max()) > 0
        np.testing.assert_allclose(np.asarray(cache[name]),
                                   np.asarray(whole[name]),
                                   rtol=1e-4, atol=2e-5)


def test_whole_experts_stream_once_and_stored_keys_are_read_once():
    """128 rows of top-4 of 32 touch every expert: 32 x 3 x 2048 x 1792 bf16
    = 704.6 MB a layer, 0.86 ms at 819 GB/s, eleven layers 7.75 GB; a cached
    token is 2,048 B a layer whatever the 32 query heads that share it."""
    cost = costs_lfm2_moe.expert_stream_cost(128, 32, 4, 2048, 1792)
    assert 31.999 < cost["experts_touched"] <= 32
    weights = 32 * 3 * 2048 * 1792 * 2
    assert weights == 704643072
    assert cost["bytes"] == pytest.approx(weights + 2 * 128 * 2048 * 2,
                                          rel=1e-6)
    assert cost["flops"] == 128 * 4 * 3 * 2 * 2048 * 1792
    assert cost["flops"] / cost["bytes"] < 20          # bound by bytes
    assert 7.74e9 < 11 * cost["bytes"] < 7.77e9
    few = costs_lfm2_moe.expert_stream_cost(4, 32, 4, 2048, 1792)
    assert few["experts_touched"] == pytest.approx(
        32 * (1 - (1 - 4 / 32.0) ** 4))
    keys = costs_lfm2_moe.paged_decode_cost([1000, 250], 32, 64, 2048)
    assert keys["bytes"] == 2048 * 1250
    assert keys["flops"] == 4 * 32 * 64 * 1250
    assert keys["flops"] / keys["bytes"] == 4.0


def _readers():
    return {name: harness.load_by_name("layer_metrics", name)
            for name in READERS}


def _run(name, mixed, config, context=((100, 200),)):
    run = _context(name, _hand_built(name, mixed), config)
    run["counters"].update(
        slots=128, trace_steps=1, chunk_size=1, n_head=32, head_dim=64,
        kv_bytes_token_layer=2048, trace_context=[list(c) for c in context])
    return run


PREFIX = "jit(mixed_step)/decode_scan/while/body/closed_call/"


def test_the_three_readers_on_a_hand_built_trace(manifest):
    """``test_scope_reduce.py``'s trace with the family's names in it: the
    scan's kernel (2 calls, 4 us) is ``paged_decode`` as it stands there,
    its matmul fusion (3 us) sits under ``moe/experts``, its movement fusion
    (3 us) under ``shortconv/conv``."""
    mixed = dict(MIXED, **{
        "fusion.9": ("fusion", PREFIX + "moe/experts/dot_general"),
        "slice_bitcast_fusion.2": ("fusion", PREFIX + "shortconv/conv/add")})
    config = harness.Cell(manifest, CELL).config
    run = _run("lfm2-hand-built", mixed, config)
    readers = _readers()
    assert readers["shortconv_time_pct"].read(run) == \
        pytest.approx(100.0 * 3 * US / run["trace"]["busy_s"])
    # 2 calls of 3 an iteration: two thirds of an iteration, 11 expert layers
    cost = costs_lfm2_moe.expert_stream_cost(128, 32, 4, 2048, 1792)
    assert readers["expert_stream_roofline"].read(run) == pytest.approx(
        100.0 * (2 / 3.0) * 11 * (cost["bytes"] / 819e9) / (3 * US))
    # 2 calls, each reading the counted contexts ONE STEP BACK (99 + 199
    # tokens of 2,048 B), over the kernel's own 4 us
    assert readers["paged_decode_roofline"].read(run) == pytest.approx(
        100.0 * 2 * (2048 * 298 / 819e9) / (4 * US))


def test_the_readers_return_nothing_for_a_program_without_the_regions(
        manifest):
    """A parent commit's trace (it cannot run the cell, but the readers run
    on every trace), or another family's: nothing raises, nothing is
    reported."""
    config = harness.Cell(manifest, CELL).config
    # the scan's kernel is another family's: no ``paged_decode`` call
    mixed = dict(MIXED, **{
        "paged_decode.3": ("custom-call",
                           PREFIX + "attn/latent_decode/pallas_call")})
    run = _run("lfm2-no-region", mixed, config)
    assert {n: r.read(run) for n, r in _readers().items()} == \
        dict.fromkeys(READERS)
    # the expert region without the kernel that counts the iterations
    mixed = dict(mixed, **{
        "fusion.9": ("fusion", PREFIX + "moe/experts/dot_general")})
    run = _run("lfm2-no-kernel", mixed, config)
    assert _readers()["expert_stream_roofline"].read(run) is None
    # the kernel, and no context counted
    run = _run("lfm2-no-context", dict(MIXED), config, context=())
    assert _readers()["paged_decode_roofline"].read(run) is None
    # a cell that holds a SHARE of its experts, with every region and the
    # kernel: the readers go by what the configuration holds, so the whole
    # experts' stream is not read, and the kernel, which is the same, is
    granite = harness.Cell(manifest, "serve-granite4h-decode-closed").config
    mixed = dict(MIXED, **{
        "fusion.9": ("fusion", PREFIX + "moe/experts/dot_general")})
    run = _run("lfm2-other-family", mixed, granite)
    assert _readers()["expert_stream_roofline"].read(run) is None
    assert _readers()["paged_decode_roofline"].read(run) == pytest.approx(
        100.0 * 2 * (2048 * 298 / 819e9) / (4 * US))


def test_the_names_file_brings_the_regions_and_no_kernel():
    from benchmark import scope_reduce

    names = scope_reduce.scope_names()
    assert {"shortconv", "in_proj", "conv", "out_proj", "qk_norm",
            "experts"} <= set(names["scopes"])
    assert {"paged_decode", "prefill_attn"} <= set(names["kernels"])
    family = harness.load_json(os.path.join(
        harness.ROOT, "benchmark", "names", "lfm2_moe.json"))
    assert "kernels" not in family and "classes" not in family


# ------------------------------------- what holds the stated precision


@pytest.fixture(scope="module")
def probed(builder):
    """The stand-in in bf16, as the cell serves it, with what the
    reference shows of one conv layer and the attention layer."""
    model = _tiny(builder, "bfloat16")
    params = model.init_params(5)
    ids = np.random.RandomState(1).randint(0, model.vocab_size, (1, 300))
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
    assert r["router_logit_err"] < 1e-5
    # bf16 values from bf16 matmuls, under limits set at the cell's widths
    for name in ("tail_rel_err", "attention_rel_err", "expert_rel_err",
                 "dense_rel_err"):
        assert 1e-4 < r[name] < dict(builder.Precision.LIMITS)[name], name


@pytest.mark.parametrize("lower", probe_lfm2_moe.CONTROLS)
def test_the_precision_below_fails_its_probe_and_no_other(
        builder, probed, lower):
    """``probe_lfm2_moe.py``'s controls at the stand-in's size: the tail
    carried in fp8, the router's matmul in bf16, the keys and values rounded
    to 8 bits as they are written, the experts' and the dense layer's
    matrices rounded to 8 bits: the comparison that holds it reads over its
    limit, the others stay inside theirs, and the program is itself again
    afterwards."""
    model, params, _, seen = probed
    shown = [(layer, 0, seen[layer]) for layer in sorted(seen)]
    with probe_lfm2_moe.planted(builder, lower) as over:
        ok, readings = probe_lfm2_moe.readings(builder, params, model.cfg,
                                               shown)
    limits = dict(builder.Precision.LIMITS)
    for name, reading in readings.items():
        assert (reading > limits[name]) == (name == over), (name, reading)
    assert not ok
    assert probe_lfm2_moe.readings(builder, params, model.cfg, shown)[0]


def test_a_failed_probe_puts_every_position_outside_the_margin(
        builder, probed, monkeypatch):
    model, params, ids, _ = probed
    monkeypatch.setattr(builder.Precision, "LIMITS", tuple(
        (name, 0.0 if name.startswith("tail") else limit)
        for name, limit in builder.Precision.LIMITS))
    out = model.reference_logits(params, jnp.asarray(ids))
    picked = np.take_along_axis(out[:, :-1], ids[:, 1:, None], axis=2)[..., 0]
    assert float((out[:, :-1].max(axis=2) - picked).min()) > 100.0


def test_the_probe_script_finds_no_fault_at_the_stand_ins_size(builder):
    out = probe_lfm2_moe.probe(builder, _tiny(builder, "bfloat16"), 5, 1, 160)
    assert out["faults"] == [] and set(out["below"]) == set(
        probe_lfm2_moe.CONTROLS)
    # the rounding model's noise, an expert layer: of bf16's order
    assert len(out["logit_noise"]) == 3
    assert all(2e-3 < n < 5e-2 for n in out["logit_noise"])
    # whatever the replay kept differently stood near the edge, and was
    # followed
    assert out["followed"]["differ"] == out["followed"]["followed"] > 0
    assert out["followed"]["furthest_sigmas"] < builder.FOLLOW_SIGMAS
    assert out["followed"]["beyond_sigmas"][str(builder.FOLLOW_SIGMAS)] == 0


def test_sides_measures_each_expert_from_the_edge_of_the_choice(builder):
    # scores 0.9, 0.7, 0.69, 0.2 with k = 2: the edge is 0.7 against 0.69
    score = np.array([[0.9, 0.7, 0.69, 0.2]])
    logits = np.log(score / (1 - score)).astype(np.float32)
    inside, far = builder.sides(logits, np.zeros(4), 2, 0.01)
    assert inside.tolist() == [[True, True, False, False]]
    slope = score * (1 - score)
    want = 0.01 / (0.01 * np.hypot(slope[0, 1], slope[0, 2]))
    np.testing.assert_allclose(far[0, 1:3], [want, want], rtol=1e-4)
    # the best stands from the first left out, the worst from the last kept
    assert far[0, 0] > 10 * want and far[0, 3] > 10 * want
    # the bias chooses: it moves the edge and no slope
    inside, _ = builder.sides(logits, np.array([0, 0, 0.05, 0]), 2, 0.01)
    assert inside.tolist() == [[True, False, True, False]]


def test_the_reference_keeps_the_experts_it_is_told_and_rounds_when_asked(
        builder):
    model = _tiny(builder)
    params = model.init_params(3)
    cfg = model.cfg
    ids = np.random.RandomState(2).randint(0, model.vocab_size, (1, 20))
    plain = builder.reference_logits(params, ids, cfg)
    own = {}

    def same(layer, b, logits):
        inside, _ = builder.sides(
            np.asarray(logits), np.asarray(
                params["moe"]["router_bias"][layer - cfg.dense_layers]),
            cfg.experts_per_token, 1.0)
        own[layer] = np.argsort(~inside, axis=-1, kind="stable")[
            :, :cfg.experts_per_token]
        return own[layer]

    again = builder.reference_logits(params, ids, cfg, follow=same)
    np.testing.assert_allclose(again, plain, atol=1e-6)
    assert sorted(own) == [1, 2, 3]

    # other experts for one token in one layer: that token's logits move,
    # and no earlier token's
    def other(layer, b, logits):
        chosen = same(layer, b, logits)
        if layer == 2:
            chosen[11] = (chosen[11] + 1) % cfg.n_experts
        return chosen

    moved = np.abs(builder.reference_logits(params, ids, cfg, follow=other)
                   - plain).max(-1)[0]
    assert moved[11] > 1e-3 and moved[:11].max() < 1e-6
    # the rounding model moves every logit a little, and only when asked
    rounded = builder.reference_logits(params, ids, cfg, round="bfloat16")
    assert 1e-4 < np.abs(rounded - plain).max() < 0.3


def _served(model, params, prompt, n):
    """``prompt`` [1, P] and ``n`` greedy tokens of the PROGRAM after it,
    padded with zeros to a fixed width: [1, P + n + 8]."""
    apply = jax.jit(model.module.apply)
    ids = np.zeros((1, prompt.shape[1] + n + 8), np.int32)
    ids[:, :prompt.shape[1]] = prompt
    for t in range(prompt.shape[1], prompt.shape[1] + n):
        ids[0, t] = int(np.asarray(apply({"params": params},
                                         jnp.asarray(ids)))[0, t - 1].argmax())
    return ids


def test_the_replay_keeps_what_the_program_keeps_and_a_wrong_token_shows(
        builder):
    """The stand-in in bf16, as the cell serves it: the reference follows
    the experts the program's decode replay kept, every followed one stood
    near the edge, the served tokens are then the reference's own within
    the driver's margin at the positions the band leaves held, and a token
    the model did not choose reads over it there."""
    model = _tiny(builder, "bfloat16")
    params, cfg = model.init_params(7), model.cfg
    prompt = np.random.RandomState(4).randint(0, model.vocab_size, (1, 12))
    ids = _served(model, params, prompt, 40)
    kept = builder.replay(params, cfg, ids, rows=4)
    assert kept.shape == (3, 1, ids.shape[1], cfg.experts_per_token)
    # idle rows beside the live one change nothing a live row computes
    assert (builder.replay(params, cfg, ids, rows=2) == kept).all()

    def margins(ids):
        held = builder.Precision(params, cfg, builder.replay(params, cfg,
                                                             ids, rows=4))
        out = builder.reference_logits(params, ids, cfg, follow=held.follow)
        picked = np.take_along_axis(out[:, :-1], ids[:, 1:, None], 2)[..., 0]
        return (out[:, :-1].max(-1) - picked)[0], \
            held.ties(ids.shape)[0, :-1], held.routing()

    margin, exempt, routing = margins(ids)
    answer = np.arange(11, 51)             # positions that predict a token
    held = answer[~exempt[answer]]
    assert routing["not_followed"] == 0 and len(held) >= 20
    assert margin[held].max() <= 0.1
    # one served token swapped for another: held there
    wrong = ids.copy()
    at = int(held[len(held) // 2])
    wrong[0, at + 1] = (wrong[0, at + 1] + 97) % model.vocab_size
    assert margins(wrong)[0][at] > 0.1
