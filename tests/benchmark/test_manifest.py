"""BENCHMARK.json against the parts of the contract a test can hold it to,
and against the files it names."""

import os
import re

import pytest

from benchmark import harness
from tests.benchmark import tiny

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
        # each is found by name under ``paths``, as the harness finds it
        harness._find(manifest["paths"], "drivers",
                      cell.traffic["kind"] + ".py")
        harness._find(manifest["paths"], "model_builders",
                      cell.config["model_type"] + ".py")
        assert cell.config["deployment"]["chips"] == row["chips"] or \
            row["chips"] == 1
    for metric in manifest["per_layer"]:
        harness._find(manifest["paths"], "layer_metrics",
                      metric["name"].rsplit(".", 1)[-1] + ".py")


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


def _spelled_alike(layer):
    """What two spellings of one layer share: case, spacing, hyphens and a
    plural do not make another layer."""
    return [w[:-1] if w.endswith("s") else w
            for w in re.split(r"[\s_-]+", layer.strip().lower())]


def test_one_layer_one_spelling(manifest):
    """A layer is spelled once: a new layer is welcome (``PERF.md`` lists
    it), a second spelling of one that is there is not."""
    spellings = {}
    for metric in manifest["per_layer"]:
        assert metric["layer"] == metric["layer"].strip() \
            and "\n" not in metric["layer"]
        spellings.setdefault(tuple(_spelled_alike(metric["layer"])),
                             set()).add(metric["layer"])
    twice = [sorted(s) for s in spellings.values() if len(s) > 1]
    assert not twice, "one layer under two spellings: {}".format(twice)
    assert _spelled_alike("Serving  engines") == \
        _spelled_alike("serving-engine") == ["serving", "engine"]


def test_every_cell_has_a_stand_in(manifest):
    """The tests run a cell through its tiny stand-in, one file a cell; a
    cell without one is run by no test."""
    found = tiny.standins()
    missing = [tiny.standin_file(w["name"]) for w in manifest["workloads"]
               if w["name"] not in found]
    assert not missing, (
        "a cell of BENCHMARK.json has no stand-in: add {} (benchmark/"
        "README.md, \"Adding a model family and its cell\")".format(
            ", ".join(missing)))
    for cell in manifest["workloads"]:
        check_stand_in(manifest, cell, found[cell["name"]])


def check_stand_in(manifest, cell, standin):
    """A stand-in against the cell it stands for."""
    assert standin["chips"] == cell["chips"], standin["file"]
    assert standin["cases"], standin["file"]
    # what a CPU cannot report is a metric of the cell, with a reason
    mine = {m["name"] for m in manifest["per_layer"]
            if cell["name"] in _cells_of(manifest, m)}
    assert set(standin["absent_on_cpu"]) <= mine, standin["file"]
    assert all(standin["absent_on_cpu"].values()), standin["file"]
    assert set(standin.get("traced_readings", {})) <= mine, standin["file"]
