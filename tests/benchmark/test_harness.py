"""The harness end to end on the CPU, at a tiny size: the drivers take the
cell as data, so the tests hand them tiny cells of their own
(``tests/benchmark/tiny.py``); ``run.py`` itself refuses to run without a TPU.
"""

import copy
import json
import os
import subprocess
import sys

import pytest

from benchmark import costs, harness
from tests.benchmark import tiny


@pytest.fixture
def cpu_peaks(monkeypatch):
    # The CPU has no row in peaks.json, and must not get one.
    monkeypatch.setattr(costs, "device_peaks", lambda kind: tiny.CPU_PEAKS)


@pytest.mark.parametrize("standin", tiny.cases("untraced"))
def test_untraced_run_reports_the_cells_end_to_end_metrics(standin):
    tiny.check_untraced(tiny.manifest(), standin)


@pytest.mark.parametrize("standin", tiny.cases("traced"))
def test_traced_run_reports_per_layer_metrics_and_a_breakdown(
        standin, cpu_peaks):
    tiny.check_traced(tiny.manifest(), standin)


def _note(capsys, event):
    """The run's last ``{"event": event, ...}`` line."""
    return [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if '"event": "{}"'.format(event) in line][-1]


@pytest.mark.parametrize("standin", tiny.cases("sharded"))
def test_zero_cell_checks_that_the_moments_are_sharded(standin, capsys):
    result = tiny.run(tiny.manifest(), standin["cell"], trace=0)
    assert result["correct"] is True
    assert result["device"]["count"] >= standin["chips"] > 1
    closed = _note(capsys, "window_closed")
    assert closed["checks"]["moments_sharded"] is True
    assert closed["checks"]["moment_leaves_whole"] == 0
    assert closed["compiles_in_window"] == 0


def test_a_fifth_cell_is_only_new_files_and_entries(cpu_peaks):
    """What a later PR does: BENCHMARK.json as it stands plus one
    configuration, one traffic mix, one cell and one per-layer metric, each
    a new file under ``paths`` and a new entry; no existing file edited."""
    manifest = copy.deepcopy(harness.load_json(harness.MANIFEST))
    cells_before = len(manifest["workloads"])
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
    assert len(manifest["workloads"]) == cells_before + 1

    plain = tiny.run(manifest, "train-tiny-fifth", trace=0)
    assert plain["correct"] is True
    assert set(plain["metrics"]) == {"train_tok_s_chip", "setup_s"}
    traced = tiny.run(manifest, "train-tiny-fifth", trace=1)
    assert set(traced["metrics"]) == {"steps_counted"}
    assert traced["metrics"]["steps_counted"] == {
        "value": float(traced["attempted"]), "unit": "steps"}


@pytest.mark.parametrize("traffic,some_gaps", [("tiny-open", True),
                                               ("tiny-open-onestep", False)])
def test_a_request_delivered_in_one_step_is_counted_not_read_as_zero(
        traffic, some_gaps, capsys):
    """``serve_tpot_p50_ms`` is over requests with a gap to measure: one
    whose tokens all reached the host in one step (an output no longer than
    about ``chunk_size``) is counted in ``tpot_one_step`` and left out of
    the median. Where every request is such a one, no median is reported:
    never a 0."""
    manifest = tiny.manifest()
    manifest["workloads"].append({
        "name": "serve-tiny-gaps", "config": "gpt2-tiny", "traffic": traffic,
        "chips": 1, "why": "tests"})
    for metric in manifest["end_to_end"]:
        if metric["name"] == "serve_tpot_p50_ms":
            metric["workloads"].append("serve-tiny-gaps")
    result = tiny.run(manifest, "serve-tiny-gaps", trace=0, seconds=2.0)
    window = _note(capsys, "window")
    assert result["correct"] is True and window["finished"] > 0
    assert window["tpot_one_step"] >= 1
    assert window["tpot_samples"] + window["tpot_one_step"] <= \
        window["finished"]
    if some_gaps:
        assert window["tpot_samples"] >= 1
        assert result["metrics"]["serve_tpot_p50_ms"]["value"] > 0
    else:
        assert window["tpot_samples"] == 0
        assert window["tpot_one_step"] == window["finished"]
        assert "serve_tpot_p50_ms" not in result["metrics"]


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
