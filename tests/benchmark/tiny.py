"""The tests' own cells: ``BENCHMARK.json`` as it is, with every cell swapped
for its tiny STAND-IN. A stand-in is one file, ``standins/<real cell>.json``
under ``paths``: the tiny cell's name, configuration, traffic mix and chips
(each a file under ``tests/benchmark/``, found by name as the real ones
are), which of the harness's cases run it (``cases``), the per-layer
metrics a CPU cannot report with the reason (``absent_on_cpu``), and the
range each reader of a traced run must land in (``traced_readings``). The
drivers, the readers and the harness are the real ones: a cell is data, and
so is its stand-in, so a PR that adds a cell adds its stand-in as a file and
the tests below enumerate it (benchmark/README.md).
"""

import copy
import json
import os

import pytest

from benchmark import harness, trace_reduce

_KEYS = {"cell", "config", "traffic", "chips", "cases", "absent_on_cpu"}
CASES = ("untraced", "traced", "sharded")

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def standin_file(real_cell):
    """Where the stand-in of ``real_cell`` is looked for first: the file a
    PR that adds the cell has to add."""
    return os.path.join("tests", "benchmark", "standins", real_cell + ".json")


def standins():
    """``{real cell: stand-in}`` of every ``standins/*.json`` under
    ``paths``, each with its ``file``."""
    out = {}
    for path in harness.find_all("standins", ".json"):
        body = dict(harness.load_json(path), file=path)
        if not _KEYS <= set(body) or not set(body["cases"]) <= set(CASES):
            raise ValueError("{}: a stand-in has the keys {} and cases among "
                             "{}".format(path, sorted(_KEYS), CASES))
        out[os.path.basename(path)[:-len(".json")]] = body
    return out


def manifest(real=None):
    """``real`` (``BENCHMARK.json`` without one) with every cell swapped for
    its stand-in. A cell without a stand-in is left out, here and in every
    metric's ``workloads``: ``test_manifest.py`` names the missing file."""
    m = copy.deepcopy(real or harness.load_json(harness.MANIFEST))
    found = standins()
    mine = {w["name"]: found[w["name"]] for w in m["workloads"]
            if w["name"] in found}
    m["configs"] = [
        {"name": name, "source": "tests only", "reduced": [], "why": "tests",
         "file": os.path.relpath(harness._find(
             m["paths"], "configs", name + ".json"), harness.ROOT)}
        for name in sorted({s["config"] for s in mine.values()})]
    m["workloads"] = [
        {"name": s["cell"], "config": s["config"], "traffic": s["traffic"],
         "chips": s["chips"], "why": "tests"} for s in mine.values()]
    for section in ("end_to_end", "per_layer"):
        for metric in m[section]:
            if "workloads" in metric:
                metric["workloads"] = [mine[w]["cell"]
                                       for w in metric["workloads"]
                                       if w in mine]
    return m


def standins_for(case):
    """The stand-ins of ``BENCHMARK.json``'s cells that list ``case``."""
    real = {w["name"]
            for w in harness.load_json(harness.MANIFEST)["workloads"]}
    return [s for cell, s in sorted(standins().items())
            if cell in real and case in s["cases"]]


def cases(case):
    """One ``pytest.param`` for every stand-in that lists ``case``: what a
    test of that case is parametrised over, so that a new cell's stand-in
    brings its own cases."""
    return [pytest.param(s, id=s["cell"]) for s in standins_for(case)]


def reading_cases():
    """One ``pytest.param`` (stand-in, metric, [low, high]) for every entry
    of every traced stand-in's ``traced_readings``."""
    return [pytest.param(s, metric, low_high,
                         id="{}-{}".format(s["cell"], metric))
            for s in standins_for("traced")
            for metric, low_high in s.get("traced_readings", {}).items()]


def cpu_trace_names():
    """Where the CPU backend's trace keeps what ``kernel_names.json`` finds
    on a TPU: its operations run on the host plane's XLA threads."""
    return dict(trace_reduce.kernel_names(), device_plane="^/host:CPU$",
                op_line="^tf_XLA", async_line="^$")


CPU_PEAKS = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def run(manifest, workload, trace, seconds=1.0, seed=3):
    import jax

    result = harness.run_cell(manifest, workload, seed, seconds, trace,
                              jax.devices(), trace_names=cpu_trace_names())
    json.dumps(result)  # the last line must serialise as it is
    return result


def reported(manifest, workload, section):
    return {m["name"] for m in manifest[section]
            if "workloads" not in m or workload in m["workloads"]}


def check_untraced(manifest, standin):
    """An untraced run of a stand-in reports the cell's end-to-end metrics,
    each above 0, and is correct."""
    result = run(manifest, standin["cell"], trace=0)
    assert set(result) == RESULT_KEYS
    assert set(result["device"]) == DEVICE_KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == reported(manifest, standin["cell"],
                                              "end_to_end")
    for reading in result["metrics"].values():
        assert set(reading) == {"value", "unit"} and reading["value"] > 0
    assert result["device"]["platform"] == "cpu"
    return result


def check_traced(manifest, standin):
    """A traced run of a stand-in reports every per-layer metric of the
    cell but those its file says a CPU cannot, and a breakdown. (The caller
    gives ``costs.device_peaks`` a row for the CPU: ``CPU_PEAKS``.)"""
    result = run(manifest, standin["cell"], trace=1)
    assert set(result) == RESULT_KEYS | {"breakdown"}
    assert set(result["device"]) == DEVICE_KEYS | {"busy_s", "window_s"}
    assert 0 < result["device"]["busy_s"] <= result["device"]["window_s"]
    assert result["correct"] is True
    # A reader that finds nothing to read returns nothing, and the harness
    # leaves that metric out of the line.
    assert set(result["metrics"]) == \
        reported(manifest, standin["cell"], "per_layer") \
        - set(standin["absent_on_cpu"])
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    for rows in result["breakdown"].values():
        assert 0 < len(rows) <= 10
        assert all(isinstance(n, str) and s >= 0 for n, s in rows)
    return result
