"""BENCHMARK.json against the parts of the contract a test can hold it to,
and against the files it names."""

import os
import re

import pytest

from benchmark import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def manifest():
    return harness.load_json(harness.MANIFEST)


def _cells_of(manifest, metric):
    return set(metric.get("workloads",
                          [w["name"] for w in manifest["workloads"]]))


def test_keys_names_and_units(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    names = []
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        section_names = [row["name"] for row in manifest[section]]
        assert len(section_names) == len(set(section_names))
        names += section_names
    assert all(NAME.match(n) for n in names)
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(metric["unit"]), metric
        assert metric["better"] in ("lower", "higher")
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
    for metric in manifest["end_to_end"]:
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    for row in manifest["workloads"]:
        assert set(row) == {"name", "config", "traffic", "chips", "why"}
        assert row["chips"] in (1, 4) and len(row["why"]) <= 200
        assert NAME.match(row["traffic"])


def test_cells_configs_and_chips(manifest):
    cells = manifest["workloads"]
    assert 2 <= len(cells) <= 24
    assert len({(c["config"], c["traffic"]) for c in cells}) == len(cells)
    assert {c["config"] for c in cells} == \
        {c["name"] for c in manifest["configs"]}
    assert sum(c["chips"] == 4 for c in cells) <= max(1, len(cells) // 4)


def test_every_named_file_exists_under_paths(manifest):
    for config in manifest["configs"]:
        assert any(config["file"].startswith(p + "/")
                   for p in manifest["paths"])
        body = harness.load_json(os.path.join(harness.ROOT, config["file"]))
        assert body["source"] == config["source"]
        assert body["reduced"] == config["reduced"]
    for row in manifest["workloads"]:
        cell = harness.Cell(manifest, row["name"])
        assert cell.traffic["kind"] in ("train", "serve")
        assert cell.config["deployment"]["chips"] == row["chips"] or \
            row["chips"] == 1
    for metric in manifest["per_layer"]:
        reader = metric["name"].rsplit(".", 1)[-1] + ".py"
        assert os.path.exists(os.path.join(
            harness.ROOT, "benchmark", "layer_metrics", reader)), reader


def test_every_cell_reports_what_the_contract_asks(manifest):
    end_to_end = {m["name"]: m for m in manifest["end_to_end"]}
    assert "workloads" not in end_to_end["setup_s"]
    assert end_to_end["setup_s"]["bound"] <= 0.1
    for row in manifest["workloads"]:
        mine = [m for m in manifest["end_to_end"]
                if row["name"] in _cells_of(manifest, m)]
        assert len(mine) >= 2, row["name"]
        assert any(row["name"] in _cells_of(manifest, m)
                   for m in manifest["per_layer"])
    for metric in manifest["per_layer"]:
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        moved = end_to_end[metric["moves"]]
        # a per-layer metric is reported only where the metric it moves is
        assert _cells_of(manifest, metric) <= _cells_of(manifest, moved)


def test_one_layer_one_spelling(manifest):
    assert {m["layer"] for m in manifest["per_layer"]} == {
        "load generator", "training engine", "sharding", "serving engine",
        "kernels", "device"}
