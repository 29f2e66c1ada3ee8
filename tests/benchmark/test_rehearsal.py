"""What the first PR that adds a model family will do, done here with files
of the tests' own and through the functions the real cells' tests use: to
``BENCHMARK.json`` as it stands, entries only; under ``paths``, new files
only (each named below; none that was there is touched, and no module's
table is patched):

    configs/rehearsal-tiny.json            a configuration of a second model_type
    model_builders/rehearsal.py            its builder
    reference/rehearsal.py                 its plain reference
    names/rehearsal.json                   one region word, one kernel, its class
    workloads/rehearsal-closed.json        a mix with a token source found by name
    traffic_sources/rehearsal_skewed.py    that token source
    layer_metrics/rehearsal_gate_time_pct.py  a reader of the new word
    standins/serve-rehearsal-closed.json   the new cell's tiny stand-in
"""

import copy
import os

import numpy as np
import pytest
from jax.profiler import ProfileData

from benchmark import costs, harness, scope_reduce, trace_reduce, traffic
from tests.benchmark import test_manifest, tiny
from tests.benchmark.test_scope_reduce import MIXED, US, trace_text

CELL = "serve-rehearsal-closed"
# What the new cell reports of what is there (a family PR appends its cell's
# name to the ``workloads`` of each), beside the metric it brings.
REPORTS = ("serve_tok_s", "decode.engine_step_ms", "decode.slot_occupancy_pct",
           "decode.kernel_time_pct", "decode.decode_attn_roofline",
           "decode.device_idle_pct", "decode.peak_hbm_gib",
           "decode.kv_move_time_pct", "decode.step_move_time_pct",
           "decode.host_ms_step")


@pytest.fixture(scope="module")
def real():
    """``BENCHMARK.json`` plus the entries a family PR appends."""
    m = copy.deepcopy(harness.load_json(harness.MANIFEST))
    config = harness.load_json(os.path.join(
        harness.ROOT, "tests/benchmark/configs/rehearsal-tiny.json"))
    m["configs"].append({
        "name": "rehearsal-tiny", "source": config["source"],
        "file": "tests/benchmark/configs/rehearsal-tiny.json",
        "reduced": [], "why": "a second model_type"})
    m["workloads"].append({
        "name": CELL, "config": "rehearsal-tiny",
        "traffic": "rehearsal-closed", "chips": 1,
        "why": "closed loop, skewed tokens: the new family's block"})
    for metric in m["end_to_end"] + m["per_layer"]:
        if metric["name"] in REPORTS:
            metric["workloads"].append(CELL)
    m["per_layer"].append({
        "name": "rehearsal_gate_time_pct", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "expert dispatch",
        "moves": "serve_tok_s", "workloads": [CELL]})
    return m


def test_the_manifest_with_the_new_entries_passes_every_manifest_check(real):
    for check in (test_manifest.test_keys_names_and_units,
                  test_manifest.test_cells_configs_and_chips,
                  test_manifest.test_every_named_file_exists_under_paths,
                  test_manifest.test_every_cell_reports_what_the_contract_asks,
                  test_manifest.test_one_layer_one_spelling):
        check(real)
    test_manifest.check_stand_in(real, real["workloads"][-1],
                                 tiny.standins()[CELL])
    assert "expert dispatch" in {m["layer"] for m in real["per_layer"]}


def test_the_new_cells_stand_in_runs_untraced_and_traced(real, monkeypatch):
    standin = tiny.standins()[CELL]
    manifest = tiny.manifest(real)
    assert harness.Cell(manifest, standin["cell"]).config["model_type"] == \
        "rehearsal"
    plain = tiny.check_untraced(manifest, standin)
    assert set(plain["metrics"]) == {"serve_tok_s", "setup_s"}
    monkeypatch.setattr(costs, "device_peaks", lambda kind: tiny.CPU_PEAKS)
    traced = tiny.check_traced(manifest, standin)
    assert "decode.step_move_time_pct" in traced["metrics"]
    assert "rehearsal_gate_time_pct" in tiny.reported(
        manifest, standin["cell"], "per_layer")


def test_the_mix_draws_its_tokens_from_the_source_it_names():
    mix = harness.load_json(os.path.join(
        harness.ROOT, "tests/benchmark/workloads/rehearsal-closed.json"))
    assert mix["tokens"] == "rehearsal_skewed"
    reqs = traffic.requests(5, 1, 8, mix, 1024)
    again = traffic.requests(5, 1, 8, mix, 1024)
    assert all(np.array_equal(p, q) for (p, _), (q, _) in zip(reqs, again))
    tokens = np.concatenate([p for p, _ in reqs])
    assert tokens.dtype == np.int32
    # skewed: the first ranks of the vocabulary take most of the draws
    assert np.mean(tokens < 32) > 0.4
    # the source draws the tokens and nothing else: the lengths stay
    uniform = traffic.requests(5, 1, 8, dict(mix, tokens="uniform"), 1024)
    assert [len(p) for p, _ in reqs] == [len(p) for p, _ in uniform]
    assert np.mean(np.concatenate([p for p, _ in uniform]) < 32) < 0.2
    with pytest.raises(ValueError, match="unknown token source"):
        traffic.requests(5, 1, 8, dict(mix, tokens="no_such_source"), 1024)


@pytest.fixture(scope="module")
def hand_built():
    """``test_scope_reduce.py``'s hand-built trace with the family's names
    in it: the scan's matmul fusion traced under the new word, the lane's
    kernel renamed to the new kernel. Written where a traced run of a cell
    called ``rehearsal-hand-built`` would have left it."""
    mixed = dict(MIXED, **{"fusion.9": (
        "fusion", "jit(mixed_step)/decode_scan/while/body/closed_call/mlp/"
        "rehearsal_gate/dot_general")})
    folder = os.path.join(harness.OUT_DIR, "trace", "rehearsal-hand-built")
    os.makedirs(folder, exist_ok=True)
    path = os.path.join(folder, "hand.xplane.pb")
    with open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(
            trace_text(mixed).replace("prefill_attn", "rehearsal_matmul")))
    return path


def test_the_new_word_and_the_new_kernel_are_read_from_a_trace(hand_built):
    reduced = scope_reduce.reduce_scopes(hand_built, ["/device:TPU:0"])
    assert reduced["scope_s"]["decode_scan/mlp/rehearsal_gate"] == \
        pytest.approx(3 * US)
    assert "rehearsal_gate" in reduced["regions"]
    assert reduced["kernels"]["rehearsal_matmul"] == {
        "s": pytest.approx(3 * US), "calls": 1}
    # the class of names/rehearsal.json, beside the benchmark's own
    trace = trace_reduce.reduce_trace(trace_reduce.load(hand_built))
    assert trace["classes"] == pytest.approx({
        "pallas": 7 * US, "decode_attn": 4 * US, "rehearsal_matmul": 3 * US})

    # and the new per-layer metric's reader, handed what the harness hands it
    class Cell(object):
        name = "rehearsal-hand-built"

    reader = harness.load_by_name("layer_metrics", "rehearsal_gate_time_pct")
    assert reader.read({"cell": Cell, "trace": trace}) == \
        pytest.approx(100.0 * 3 * US / trace["busy_s"])
