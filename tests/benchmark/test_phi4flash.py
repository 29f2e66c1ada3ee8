"""PR 55's configuration, builder, reference, probes, readers and cell: the
``phi4flash`` family (window layers on a ring of pages beside ONE full layer
whose plane eight layers read, gated memory units fed by a Mamba layer's scan)
against the contract a test can hold it to. The model itself is held to the
reference in ``tests/unit/test_sambay.py``; the cell's stand-in runs with the
others in ``test_harness.py``. Pins NEITHER that its entries stand last in
``BENCHMARK.json`` NOR that a shared reader lists its cell alone."""

import importlib.util
import os

import jax
import numpy as np
import pytest

from benchmark import (costs, costs_phi4flash, harness, scope_reduce,
                       setup_reduce)
from tests.benchmark import test_setup_phases as setup_pins
from tests.benchmark import tiny

CELL, CONFIG = "serve-phi4flash-decode-closed", "phi-4-mini-flash-reasoning"
SOURCE = ("https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/"
          "blob/main/config.json")
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}
NEW_READERS = {
    "window_attn_time_pct": ("lower", "window attention"),
    "window_decode_roofline": ("higher", "kernels"),
    "shared_kv_time_pct": ("lower", "window attention"),
    "shared_kv_decode_roofline": ("higher", "kernels"),
    "gmu_time_pct": ("lower", "state-space mixer"),
}
SHARED_READERS = (
    "decode.engine_step_ms", "decode.slot_occupancy_pct",
    "decode.kernel_time_pct", "decode.device_idle_pct",
    "decode.peak_hbm_gib", "decode.kv_move_time_pct", "decode.host_ms_step",
    "decode.step_move_time_pct", "mamba1_time_pct", "mlp_time_pct",
    "setup_boot_s", "setup_engine_init_s", "setup_trace_s", "setup_lower_s",
    "setup_compile_s", "setup_warm_s", "setup_programs",
    "setup_cache_misses")

builder = harness.load_by_name("model_builders", "phi4flash")


@pytest.fixture(scope="module")
def cell():
    return harness.Cell(harness.load_json(harness.MANIFEST), CELL)


@pytest.fixture(scope="module")
def tiny_config():
    return harness.load_json(harness._find(
        harness.paths(), "configs", "phi4flash-tiny.json"))


def test_the_configuration_keeps_every_published_key_and_cuts_nothing(cell):
    config = cell.config
    assert {k: config[k] for k in PUBLISHED} == PUBLISHED
    assert config["reduced"] == [] and "NOTHING IS CUT" in \
        config["reduced_why"]
    assert set(config["assumed"]) >= {
        "mamba_sizes", "mamba_biases", "layer_order", "layer_norm",
        "attention_bias", "window", "positions", "memory", "dtypes",
        "final_norm_init"}
    assert set(config["departures"]) == {"differential_attention"}
    assert config["deployment"]["chips"] == 1 == \
        config["deployment"]["stands_for_chips"]
    assert config["deployment"]["residual_dtype"] == "float32"
    row, = [c for c in cell.manifest["configs"] if c["name"] == CONFIG]
    assert row["reduced"] == [] and row["source"] == config["source"] \
        == SOURCE
    assert cell.traffic_name == "window-decode-closed" and cell.chips == 1
    assert [w["name"] for w in cell.manifest["workloads"]
            if w["chips"] == 4] == ["train-gpt2xl-zero-dp4"]
    assert len(cell.manifest["workloads"]) >= 12


def test_the_traffic_is_the_issues_letter_for_letter(cell):
    mix = cell.traffic
    assert (mix["kind"], mix["loop"], mix["clients"], mix["request_pool"],
            mix["sampling"], mix["tokens"], mix["schedule_seed"]) == (
        "serve", "closed", 96, 384, "stratified", "uniform", 1)
    assert mix["prompt"] == {"median": 64, "sigma": 0.5, "min": 32,
                             "max": 128}
    assert mix["output"] == {"median": 2304, "sigma": 0.15, "min": 2048,
                             "max": 2816}
    assert mix["engine"] == {
        "max_slots": 96, "max_len": 2944, "chunk_size": 16, "paged_kv": True,
        "kv_page_len": 128, "prefill_chunk": 128, "max_queue": 96}
    assert mix["trace_steps"] == 8 and mix["rate_chunk_steps"] == 8
    # latent-decode-closed's lengths
    other = harness.Cell(cell.manifest, "serve-jamba2-decode-closed").traffic
    assert (mix["prompt"], mix["output"]) == (other["prompt"],
                                              other["output"])


def test_the_cells_exact_metric_set(cell):
    metrics = {m["name"]: m for m in cell.manifest["per_layer"]}
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "serve_tok_s", "setup_s"}
    assert {m["name"] for m in cell.metrics("per_layer")} == \
        set(NEW_READERS) | set(SHARED_READERS)
    for name, (better, layer) in NEW_READERS.items():
        m = metrics[name]
        assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"],
                m["workloads"]) == ("%", better, "device_trace", layer,
                                    "serve_tok_s", [CELL])
    # readers of another family's own keys, and the ones that multiply a
    # FULL context by every attention layer
    for name in ("selective_scan_roofline", "paged_decode_roofline",
                 "decode.decode_attn_roofline"):
        assert CELL not in metrics[name]["workloads"]
    standin = tiny.standins()[CELL]
    assert standin["cases"] == ["untraced", "traced"]
    assert set(standin["absent_on_cpu"]) == {
        "window_decode_roofline", "shared_kv_decode_roofline",
        "decode.peak_hbm_gib"}
    assert set(standin["traced_readings"]) >= {
        "window_attn_time_pct", "shared_kv_time_pct", "gmu_time_pct",
        "mamba1_time_pct", "mlp_time_pct"}


def test_the_builders_tree_counts_the_issues_3_852_556_800(cell):
    """Shapes only: the tree ``init_inference`` is handed at the published
    keys, against the issue's arithmetic and the builder's own count."""
    model = builder.Model(cell.config)
    cfg = model.cfg
    tree = jax.eval_shape(lambda: model.module.init(
        jax.random.PRNGKey(0))["params"])
    count = sum(int(np.prod(a.shape))
                for a in jax.tree_util.tree_leaves(tree))
    assert count == 3852556800 == model.sizes()["params"]
    assert 9 * 119895040 + 9 * 98321920 + 7 * 104867840 + 7 * 91765760 \
        + 512163840 + 5120 == count
    parts = builder.parameter_count(cfg)
    assert parts["mamba1"] == 9 * 41241600
    assert parts["attention"] == 9 * 19668480
    assert parts["gmu"] == 7 * 26214400 and parts["xattn"] == 7 * 13112320
    assert parts["every_layer"] == 32 * (78643200 + 10240)
    assert cfg.mamba1_layers == tuple(range(0, 17, 2))
    assert cfg.window_layers == tuple(range(1, 16, 2))
    assert cfg.kv_layers == (17,) and cfg.memory_layer == 16
    assert [i for i, k in enumerate(cfg.kinds) if k == "gmu"] == \
        list(range(18, 32, 2))
    assert [i for i, k in enumerate(cfg.kinds) if k == "xattn"] == \
        list(range(19, 32, 2))
    assert all(cfg.kv_plane(i) == 0 for i in range(17, 32, 2))
    assert sorted(tree["layers"]) == ["attn_norm", "attn_norm_b",
                                      "ffn_norm", "ffn_norm_b"]
    assert "dt_norm" not in tree["mamba1"] and "lm_head" not in tree
    assert tree["mamba1"]["A_log"].shape == (9, 16, 5120)
    assert tree["swa"]["wqkv"].shape == (8, 2560, 5120)
    assert tree["attn"]["wqkv"].shape == (1, 2560, 5120)
    assert tree["xattn"]["wq"].shape == (7, 2560, 2560)
    assert tree["gmu"]["w_in"].shape == (7, 2560, 5120)
    assert model.kv_bytes_per_token_layer() == 5120
    assert model.sizes()["state_bytes_per_slot"] == 9 * 358400


def test_the_cells_pool_holds_a_ring_of_six_pages_a_slot(cell):
    """Shapes only: the pool of the cell's engine. A window layer's ring is
    6 pages a slot (5 hold a decode step's window; the lane's slice of up to
    128 positions is written before it is read, and must not land on a page
    its first query still sees), whatever ``max_len``."""
    from deepspeed_tpu.inference import kv_pool
    from deepspeed_tpu.models import decoder
    from deepspeed_tpu.ops.transformer.kernels import decode_attention as da

    assert da.ring_pages(512, 128) == 5 and da.ring_pages(512, 128, 128) == 6
    spec = decoder.cache_spec(builder.Model(cell.config).cfg)
    engine = cell.traffic["engine"]
    pools = [jax.eval_shape(lambda n=n: kv_pool.init_pool(
        spec, engine["max_slots"], n, slack=engine["prefill_chunk"],
        page_len=engine["kv_page_len"])) for n in (2944, 32768)]
    for pool in pools:
        assert pool["wk"].shape == (8, 1 + 96 * 6, 10, 128, 128)
    assert pools[0]["k"].shape == (1, 1 + 96 * 24, 10, 128, 128)
    rings = sum(int(np.prod(pools[0][n].shape)) * 2 for n in ("wk", "wv"))
    assert abs(rings - 8 * 96 * 6 * 128 * 5120) < 8 * 2 * 2 ** 20


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(harness.ROOT, "benchmark", "reference",
                        "phi4flash.py")
    source = open(path).read()
    assert "import deepspeed_tpu" not in source
    assert "from deepspeed_tpu" not in source
    assert 'default_matmul_precision("highest")' in source
    assert "jax.lax.scan(token" in source          # a token at a time
    assert "DEPARTURE" in source and source.count("ASSUMED") >= 6


def test_a_program_without_the_window_group_is_refused_at_once(cell,
                                                               monkeypatch):
    """What the parent commit does with the new cell: the builder raises
    before any weight is drawn, so the run exits non-zero in seconds."""
    from deepspeed_tpu.models import decoder

    class Parent(object):
        _fields = tuple(f for f in decoder.DecoderConfig._fields
                        if f != "sliding_window")

    monkeypatch.setattr(decoder, "DecoderConfig", Parent)
    with pytest.raises(RuntimeError, match="no window group"):
        builder.Model(cell.config)


def test_the_probes_hold_the_sound_program_and_catch_each_precision_below(
        tiny_config):
    """``probe_phi4flash.py`` at the stand-in's size (a sequence of 300
    tokens: past two pages of 128, so the probe's ring of 3 pages wraps):
    the five readings of the sound program are under their limits, and each
    planted precision reads over ITS limit and no other."""
    spec = importlib.util.spec_from_file_location(
        "probe_phi4flash", os.path.join(harness.ROOT, "benchmark",
                                        "probe_phi4flash.py"))
    probe = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(probe)
    out = probe.probe(builder, builder.Model(tiny_config), 7, 1, 300)
    assert out["faults"] == [], out
    assert set(out["below"]) == set(probe.CONTROLS)
    assert all(v is not None for v in out["sound"].values())


def test_the_costs_count_a_window_not_a_context():
    lens = [0, 100, 512, 2000]
    win = costs_phi4flash.window_decode_cost(lens, 512, 40, 64, 5120)
    full = costs_phi4flash.shared_decode_cost(lens, 40, 64, 5120)
    assert win["bytes"] == 5120 * (100 + 512 + 512) + 2 * 3 * 40 * 64 * 2
    assert full["bytes"] == 5120 * 2612 + 2 * 3 * 40 * 64 * 2
    assert win["flops"] == 4 * 40 * 64 * 1124
    # bound by memory on the chip the cell runs on
    peaks = costs.device_peaks("TPU v5 lite")
    assert costs.least_seconds(full["flops"], full["bytes"], peaks)[1] == \
        "memory"
    # one step back, the mean over the tail's iterations
    counters = {"trace_context": [[40, 600], []], "chunk_size": 2}
    got = costs_phi4flash.least_call_seconds(
        counters, "TPU v5 lite", lambda ls: {"flops": 0.0,
                                             "bytes": float(sum(ls))})
    assert got == pytest.approx(
        ((38 + 598) + (39 + 599)) / 2.0 / peaks["hbm_bytes_per_s"])
    assert costs_phi4flash.least_call_seconds(
        {"chunk_size": 2}, "TPU v5 lite", None) is None


@pytest.mark.parametrize("name", sorted(NEW_READERS))
def test_a_reader_finds_nothing_in_another_familys_trace(name, monkeypatch):
    """On the parent commit, and in every other cell, the program has no
    such region and no such kernel: the reader returns None and the line
    leaves the metric out."""
    reader = harness.load_by_name("layer_metrics", name)
    reduced = {"regions": ["decode_scan", "attn", "mlp", "mamba1"],
               "scope_s": {"decode_scan/attn": 1.0, "decode_scan/mlp": 2.0},
               "kernels": {"paged_decode": {"s": 1.0, "calls": 16}}}
    monkeypatch.setattr(scope_reduce, "of_run", lambda run: reduced)
    cell = harness.Cell(harness.load_json(harness.MANIFEST),
                        "serve-jamba2-decode-closed")
    run = {"cell": cell, "trace": {"busy_s": 4.0},
           "device": {"kind": "TPU v5 lite"},
           "counters": {"trace_context": [[100, 200]], "chunk_size": 16,
                        "n_head": 20, "head_dim": 128,
                        "kv_bytes_token_layer": 512}}
    assert reader.read(run) is None


# ----------------------------- what PR 53's pins held, beside this PR's cell
# (``tests/conftest.py`` SETUP_ROWS_AS_PR_53_LEFT_THEM: the twelve cases whose
# two stale lines an appended cell and five appended metrics make untrue;
# every other line of them, case for case)


@pytest.mark.parametrize("name", setup_pins.NAMES)
def test_a_setup_row_stands_as_pr_53_left_it_and_lists_this_cell(name):
    manifest = harness.load_json(harness.MANIFEST)
    rows = [m for m in manifest["per_layer"] if m["name"] == name]
    assert len(rows) == 1
    counter = name in ("setup_programs", "setup_cache_misses")
    # the eleven where they stood, this PR's cell after them
    assert rows[0] == {
        "name": name, "unit": "programs" if counter else "s",
        "better": "lower",
        "source": "program_counter" if counter else "program_span",
        "layer": "start-up", "moves": "setup_s",
        "workloads": setup_pins.CELLS + [CELL]}
    assert set(setup_pins.CELLS) <= {w["name"]
                                     for w in manifest["workloads"]}
    setup = [m for m in manifest["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]
    assert setup_reduce.reading({"setup_phases": {setup_pins.PART[name]: 7}},
                                setup_pins.PART[name]) == 7


@pytest.mark.parametrize("pin", [setup_pins._dsv3, setup_pins._kimi,
                                 setup_pins._lfm2, setup_pins._sdar],
                         ids=lambda pin: pin.__name__.strip("_"))
def test_a_pin_of_a_cells_exact_set_holds_beside_the_eight_and_the_five(pin):
    """The four pins of a cell's EXACT per-layer set, called themselves on
    the manifest without PR 53's eight rows, as
    ``test_setup_phases.py`` calls them; what stood before the eight stands
    where it stood, the eight after it, this PR's five after them (none of
    which any other cell lists)."""
    before = setup_pins._without_the_eight()
    cell = pin(before)
    now = harness.load_json(harness.MANIFEST)
    assert {m["name"] for m in harness.Cell(now, cell).metrics("per_layer")} \
        == {m["name"] for m in harness.Cell(before, cell).metrics(
            "per_layer")} | set(setup_pins.NAMES)
    stood = [m for m in before["per_layer"] if m["name"] not in NEW_READERS]
    assert now["per_layer"][:len(stood)] == stood
    assert [m["name"] for m in now["per_layer"][len(stood):]] == \
        list(setup_pins.NAMES) + list(NEW_READERS)
