"""The harness end to end on the CPU, at a tiny size: the drivers take the
cell as data, so the tests hand them tiny cells of their own
(``tests/benchmark/tiny.py``); ``run.py`` itself refuses to run without a TPU.
"""

import copy
import json
import os
import subprocess
import sys

import jax
import pytest

from benchmark import costs, harness
from tests.benchmark import tiny

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


@pytest.fixture
def cpu_peaks(monkeypatch):
    # The CPU has no row in peaks.json, and must not get one.
    monkeypatch.setattr(costs, "device_peaks", lambda kind: tiny.CPU_PEAKS)


def _run(manifest, workload, trace, seconds=1.0, seed=3):
    result = harness.run_cell(manifest, workload, seed, seconds, trace,
                              jax.devices(),
                              trace_names=tiny.cpu_trace_names())
    json.dumps(result)  # the last line must serialise as it is
    return result


def _reported(manifest, workload, section):
    return {m["name"] for m in manifest[section]
            if "workloads" not in m or workload in m["workloads"]}


@pytest.mark.parametrize("workload", [
    "train-tiny", "serve-tiny-closed", "serve-tiny-open"])
def test_untraced_run_reports_the_cells_end_to_end_metrics(workload):
    manifest = tiny.manifest()
    result = _run(manifest, workload, trace=0)
    assert set(result) == RESULT_KEYS
    assert set(result["device"]) == DEVICE_KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == _reported(manifest, workload,
                                               "end_to_end")
    for name, reading in result["metrics"].items():
        assert set(reading) == {"value", "unit"} and reading["value"] > 0
    assert result["device"]["platform"] == "cpu"


@pytest.mark.parametrize("workload, absent", [
    # flash kernels run interpreted on the CPU, so there is none to time;
    ("train-tiny-dp4", {"flash_roofline", "train.peak_hbm_gib"}),
    ("serve-tiny-closed", {"decode.decode_attn_roofline",
                           "decode.peak_hbm_gib"}),
    ("serve-tiny-open", {"chat.decode_attn_roofline", "chat.peak_hbm_gib"}),
])
def test_traced_run_reports_per_layer_metrics_and_a_breakdown(
        workload, absent, cpu_peaks):
    manifest = tiny.manifest()
    result = _run(manifest, workload, trace=1)
    assert set(result) == RESULT_KEYS | {"breakdown"}
    assert set(result["device"]) == DEVICE_KEYS | {"busy_s", "window_s"}
    assert 0 < result["device"]["busy_s"] <= result["device"]["window_s"]
    assert result["correct"] is True
    # A reader that finds nothing to read returns nothing, and the harness
    # leaves that metric out of the line.
    assert set(result["metrics"]) == \
        _reported(manifest, workload, "per_layer") - absent
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    for rows in result["breakdown"].values():
        assert 0 < len(rows) <= 10
        assert all(isinstance(n, str) and s >= 0 for n, s in rows)


def test_zero_cell_checks_that_the_moments_are_sharded(capsys):
    manifest = tiny.manifest()
    result = _run(manifest, "train-tiny-dp4", trace=0)
    assert result["correct"] is True and result["device"]["count"] >= 4
    closed = [json.loads(line) for line in capsys.readouterr().out.splitlines()
              if '"window_closed"' in line][-1]
    assert closed["checks"]["moments_sharded"] is True
    assert closed["checks"]["moment_leaves_whole"] == 0
    assert closed["compiles_in_window"] == 0


def test_a_fifth_cell_is_only_new_files_and_entries(cpu_peaks):
    """What a later PR does: BENCHMARK.json as it stands plus one
    configuration, one traffic mix, one cell and one per-layer metric, each
    a new file under ``paths`` and a new entry; no existing file edited."""
    manifest = copy.deepcopy(harness.load_json(harness.MANIFEST))
    manifest["configs"].append({
        "name": "gpt2-tiny-3layer", "source": "tests only", "reduced": [],
        "file": "tests/benchmark/configs/gpt2-tiny-3layer.json",
        "why": "tests"})
    manifest["workloads"].append({
        "name": "train-tiny-fifth", "config": "gpt2-tiny-3layer",
        "traffic": "tiny-train-t32", "chips": 1, "why": "tests"})
    for metric in manifest["end_to_end"]:
        if metric["name"] == "train_tok_s_chip":
            metric["workloads"].append("train-tiny-fifth")
    manifest["per_layer"].append({
        "name": "steps_counted", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "training engine",
        "moves": "train_tok_s_chip", "workloads": ["train-tiny-fifth"]})
    assert len(manifest["workloads"]) == 5

    plain = _run(manifest, "train-tiny-fifth", trace=0)
    assert plain["correct"] is True
    assert set(plain["metrics"]) == {"train_tok_s_chip", "setup_s"}
    traced = _run(manifest, "train-tiny-fifth", trace=1)
    assert set(traced["metrics"]) == {"steps_counted"}
    assert traced["metrics"]["steps_counted"] == {
        "value": float(traced["attempted"]), "unit": "steps"}


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError, match="not in the manifest"):
        harness.Cell(tiny.manifest(), "no-such-cell")


def test_run_py_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(harness.ROOT, "benchmark", "run.py"),
         "--workload", "train-gpt2m-1chip", "--seed", "0", "--seconds", "1",
         "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=120)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "needs 1 TPU chip" in done.stderr


def test_median_chunk_rate_ignores_one_stall():
    # 41 boundaries 1 s apart, 10 units a step: 10 units/s in 4 chunks of 10
    steady = [float(i) for i in range(41)]
    assert harness.median_chunk_rate(steady, [10] * 41, 10) == (10.0, 4)
    # a 7 s stall inside the second chunk: the whole-window rate loses 15%
    stalled = [t + (7.0 if t >= 15 else 0.0) for t in steady]
    assert 400 / (stalled[-1] - stalled[0]) == pytest.approx(8.51, abs=0.01)
    assert harness.median_chunk_rate(stalled, [10] * 41, 10) == (10.0, 4)
    # a slowdown in every chunk shows in full
    slow = [1.2 * t for t in steady]
    assert harness.median_chunk_rate(slow, [10] * 41, 10)[0] == \
        pytest.approx(10 / 1.2)
    # fewer steps than a chunk: the whole span is the one chunk
    assert harness.median_chunk_rate([0.0, 1.0, 2.0], [0, 5, 7], 10) == \
        (6.0, 1)
