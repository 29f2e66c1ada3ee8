"""The Jamba family's configuration, builder, reference and probes
(``benchmark/configs/jamba2-3b.json``, ``model_builders/jamba.py``,
``reference/jamba.py``, ``probe_jamba.py``): what ties the cell
``serve-jamba2-decode-closed`` to the published model. The model itself is
held to the reference in ``tests/unit/test_mamba1.py``; the cell's stand-in
runs with the others in ``test_harness.py``."""

import importlib.util
import os

import jax
import numpy as np
import pytest

from benchmark import harness

builder = harness.load_by_name("model_builders", "jamba")
CELL = "serve-jamba2-decode-closed"
LFM2_CELL = "serve-lfm2moe-decode-closed"
PUBLISHED = {
    "attn_layer_offset": 7, "attn_layer_period": 14,
    "expert_layer_offset": 1, "expert_layer_period": 2, "hidden_act": "silu",
    "hidden_size": 2560, "intermediate_size": 8192, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160,
    "mamba_expand": 2, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "model_type": "jamba",
    "num_attention_heads": 20, "num_experts": 1, "num_experts_per_tok": 1,
    "num_hidden_layers": 28, "num_key_value_heads": 1,
    "num_logits_to_keep": 1, "rms_norm_eps": 1e-06, "sliding_window": None,
    "tie_word_embeddings": True, "use_mamba_kernels": True,
    "vocab_size": 65536}


@pytest.fixture(scope="module")
def cell():
    return harness.Cell(harness.load_json(harness.MANIFEST), CELL)


def test_the_configuration_keeps_every_published_key_and_cuts_nothing(cell):
    config = cell.config
    assert {k: config[k] for k in PUBLISHED} == PUBLISHED
    assert config["reduced"] == [] and "NOTHING IS CUT" in \
        config["reduced_why"]
    assert set(config["assumed"]) >= {
        "head_dim", "layer_order", "inner_norms", "mamba_init", "dtypes",
        "embed_init_range", "final_norm_init"}
    assert config["deployment"]["chips"] == 1 == \
        config["deployment"]["stands_for_chips"]
    row, = [c for c in cell.manifest["configs"] if c["name"] == "jamba2-3b"]
    assert row["reduced"] == [] and row["source"] == config["source"]
    assert cell.traffic_name == "latent-decode-closed" and cell.chips == 1


@pytest.mark.parametrize("name", [
    "serve-dsv3-decode-closed", "serve-kimilinear-decode-closed",
    LFM2_CELL, CELL])
def test_the_cells_on_the_shared_traffic_file_keep_it_unchanged(cell, name):
    """Four families on ONE traffic file: at identical rows and lengths the
    differences between them are the models' own. Each is one chip and
    reports ``serve_tok_s`` and ``setup_s``; the only cell on four chips
    stays the one that was."""
    other = harness.Cell(cell.manifest, name)
    assert other.chips == 1 and other.traffic_name == "latent-decode-closed"
    assert other.traffic == harness.Cell(
        cell.manifest, "serve-dsv3-decode-closed").traffic
    assert {m["name"] for m in other.metrics("end_to_end")} == {
        "serve_tok_s", "setup_s"}
    # the two cells whose scan calls ``paged_decode`` on rows of several
    # query heads a stored head report its roofline, the latent two do not
    assert ("paged_decode_roofline" in {
        m["name"] for m in other.metrics("per_layer")}) == \
        (name in (LFM2_CELL, CELL))
    assert [w["name"] for w in cell.manifest["workloads"]
            if w["chips"] == 4] == ["train-gpt2xl-zero-dp4"]


def test_the_lfm2_cell_stands_as_pr_44_left_it(cell):
    """Every line of ``test_lfm2_moe.py::
    test_the_cell_is_one_chip_with_dsv3s_traffic_unchanged`` that an appended
    cell leaves true, held here because that test now stops at its first
    stale line (``conftest.py``): the LFM2 cell's traffic, its end-to-end
    metrics, its EXACT per-layer set, its three readers' layer, unit and
    ``moves``, and the two readers that list it alone. Its three stale
    lines said that PR 44's entries stand LAST and that
    ``paged_decode_roofline`` lists it alone; what they were there for is
    held by position: a list only grows at its end, so PR 44's entries
    stand where they stood, and ``paged_decode_roofline`` lists the LFM2
    cell first and this PR's after it."""
    manifest = cell.manifest
    readers = ("shortconv_time_pct", "expert_stream_roofline",
               "paged_decode_roofline")
    lfm2 = harness.Cell(manifest, LFM2_CELL)
    assert lfm2.chips == 1 and lfm2.traffic_name == "latent-decode-closed"
    assert lfm2.traffic == harness.Cell(
        manifest, "serve-dsv3-decode-closed").traffic
    assert {m["name"] for m in lfm2.metrics("end_to_end")} == \
        {"serve_tok_s", "setup_s"}
    reports = {m["name"] for m in lfm2.metrics("per_layer")}
    assert set(readers) | {
        "expert_time_pct", "router_time_pct", "decode.engine_step_ms",
        "decode.slot_occupancy_pct", "decode.kernel_time_pct",
        "decode.device_idle_pct", "decode.peak_hbm_gib",
        "decode.kv_move_time_pct", "decode.host_ms_step",
        "decode.step_move_time_pct"} == reports
    # it counts every layer as holding keys (three of twelve do)
    assert "decode.decode_attn_roofline" not in reports
    # nothing of this PR's is read in the LFM2 cell
    assert not reports & {"mamba1_time_pct", "selective_scan_roofline",
                          "mlp_time_pct"}
    layers = {m["name"]: m for m in manifest["per_layer"]}
    assert [layers[name]["layer"] for name in readers] == \
        ["short-convolution mixer", "expert feed-forward", "kernels"]
    for name in readers:
        assert layers[name]["moves"] == "serve_tok_s"
        assert layers[name]["unit"] == "%"
    for name in readers[:2]:
        assert layers[name]["workloads"] == [LFM2_CELL]
    assert layers["paged_decode_roofline"]["workloads"] == [LFM2_CELL, CELL]
    # PR 44's entries where they stood and this PR's next after them, by
    # index and not from the end: the next cell is appended too
    assert [c["name"] for c in manifest["configs"][6:8]] == [
        "lfm2-8b-a1b-12l", "jamba2-3b"]
    assert [w["name"] for w in manifest["workloads"][8:10]] == [
        LFM2_CELL, CELL]
    assert [m["name"] for m in manifest["per_layer"][54:60]] == list(
        readers) + ["mamba1_time_pct", "selective_scan_roofline",
                    "mlp_time_pct"]
    assert [w["name"] for w in manifest["workloads"] if w["chips"] == 4] == \
        ["train-gpt2xl-zero-dp4"]


def test_the_builders_tree_counts_the_published_3_029_337_472(cell):
    """Shapes only: the tree ``init_inference`` is handed at the published
    keys, against the issue's arithmetic and the builder's own count."""
    model = builder.Model(cell.config)
    cfg = model.cfg
    tree = jax.eval_shape(lambda: model.module.init(
        jax.random.PRNGKey(0))["params"])
    count = sum(int(np.prod(a.shape))
                for a in jax.tree_util.tree_leaves(tree))
    assert count == 3029337472 == model.sizes()["params"]
    assert 26 * 104161472 + 2 * 76682240 + 167774720 == count
    assert cfg.kv_layers == (7, 21) and len(cfg.mamba1_layers) == 26
    assert cfg.expert_layers == 0 and "moe" not in tree
    assert sorted(tree["layers"]) == ["attn_norm", "ffn_norm"]
    assert tree["mamba1"]["A_log"].shape == (26, 16, 5120)
    assert tree["mamba1"]["x_proj"].shape == (26, 5120, 192)
    assert tree["attn"]["wqkv"].shape == (2, 2560, 2560 + 2 * 128)
    assert model.kv_bytes_per_token_layer() == 512
    assert model.sizes()["state_bytes_per_slot"] == \
        26 * (16 * 5120 * 4 + 3 * 5120 * 2)


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(harness.ROOT, "benchmark", "reference", "jamba.py")
    source = open(path).read()
    assert "import deepspeed_tpu" not in source
    assert "from deepspeed_tpu" not in source
    assert 'default_matmul_precision("highest")' in source
    assert "jax.lax.scan(token" in source          # a token at a time


def test_a_program_without_the_kind_is_refused_at_once(cell, monkeypatch):
    """What the parent commit does with the new cell: the builder raises
    before any weight is drawn, so the run exits non-zero in seconds."""
    from deepspeed_tpu.models import decoder

    monkeypatch.setattr(decoder, "RECURRENT", {
        k: v for k, v in decoder.RECURRENT.items() if k != "mamba1"})
    with pytest.raises(RuntimeError, match="no Mamba-1 selective scan"):
        builder.Model(cell.config)


def test_the_probes_hold_the_sound_program_and_catch_each_precision_below():
    """``probe_jamba.py`` at the stand-in's size: the five readings of the
    sound program are under their limits, and each planted precision reads
    over ITS limit and no other."""
    spec = importlib.util.spec_from_file_location(
        "probe_jamba", os.path.join(harness.ROOT, "benchmark",
                                    "probe_jamba.py"))
    probe = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(probe)
    config = harness.load_json(harness._find(
        harness.paths(), "configs", "jamba-tiny.json"))
    out = probe.probe(builder, builder.Model(config), 7, 1, 96)
    assert out["faults"] == [], out
    assert set(out["below"]) == set(probe.CONTROLS)
    assert all(v is not None for v in out["sound"].values())


def test_the_cell_reports_its_own_readers_and_no_experts(cell):
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert {"mamba1_time_pct", "selective_scan_roofline", "mlp_time_pct",
            "paged_decode_roofline", "decode.device_idle_pct"} <= names
    assert not names & {"expert_time_pct", "router_time_pct",
                        "ssm_time_pct", "ssm_update_roofline",
                        "decode.decode_attn_roofline"}
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "serve_tok_s", "setup_s"}
