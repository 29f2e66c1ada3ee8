"""The names the reductions look for are the benchmark's two files merged
with every ``names/*.json`` under ``paths``: a family registers its region
words, its kernels and their classes by adding such a file."""

import json

import pytest

from benchmark import harness, scope_reduce, trace_reduce


@pytest.fixture
def family(tmp_path, monkeypatch):
    """Hands the merge names files of the test's own: ``family(a, b)``
    writes them and makes them what ``names/*.json`` finds."""
    def write(*bodies):
        files = []
        for i, body in enumerate(bodies):
            files.append(str(tmp_path / "family{}.json".format(i)))
            with open(files[-1], "w") as f:
                json.dump(body, f)
        monkeypatch.setattr(harness, "find_all",
                            lambda folder, suffix: list(files))
        return files
    return write


def test_without_a_family_the_names_are_the_two_files(family):
    family()
    base = harness.load_json(harness.ROOT + "/benchmark/kernel_names.json")
    assert trace_reduce.kernel_names() == base
    names = scope_reduce.scope_names()
    assert names["scopes"][:3] == ["embed", "block", "ln"]
    assert names["kernel"].startswith("^(flash_fwd|flash_bwd|paged_decode|")


def test_a_familys_file_is_merged_into_both(family):
    family({"scopes": ["router", "experts", "mlp"],
            "kernels": ["grouped_matmul"], "movement": ["gather"],
            "classes": {"grouped_matmul": ["(^|/)grouped_matmul[^/ ]* "]},
            "why": "prose is ignored"},
           {"scopes": ["router", "shared_expert"],
            "classes": {"grouped_matmul": ["(^|/)grouped_matmul[^/ ]* "]}})
    scopes = scope_reduce.scope_names()
    # united, in order, a word two files give once
    assert scopes["scopes"][-3:] == ["router", "experts", "shared_expert"]
    assert scopes["scopes"].count("mlp") == 1
    assert scopes["movement"][-1] == "gather"
    assert scopes["kernel"].endswith("|attn_softmax|grouped_matmul)")
    assert scope_reduce.scope_path(
        ["decode_scan", "while", "mlp", "router", "dot_general"],
        set(scopes["scopes"])) == "decode_scan/mlp/router"
    classes = trace_reduce.kernel_names()["classes"]
    assert set(classes) == {"pallas", "flash", "decode_attn",
                            "grouped_matmul"}
    # a names file adds to the files of this directory and never to itself
    assert "grouped_matmul" not in harness.load_json(
        harness.ROOT + "/benchmark/kernel_names.json")["classes"]


def test_a_class_defined_twice_differently_names_both_files(family):
    first, second = family({"classes": {"experts": ["a"]}},
                           {"classes": {"experts": ["b"]}})
    with pytest.raises(ValueError, match="defined twice") as e:
        trace_reduce.kernel_names()
    assert first in str(e.value) and second in str(e.value)
    # the benchmark's own classes cannot be redefined either
    clash, = family({"classes": {"decode_attn": ["while/"]}})
    with pytest.raises(ValueError, match="kernel_names.json") as e:
        trace_reduce.kernel_names()
    assert clash in str(e.value)


def test_a_key_no_reduction_reads_is_refused(family):
    path, = family({"scope": ["router"]})
    with pytest.raises(ValueError, match="no reduction reads") as e:
        scope_reduce.scope_names()
    assert path in str(e.value)


def test_the_names_files_under_paths_are_found():
    files = harness.find_all("names", ".json")
    assert any(f.endswith("tests/benchmark/names/rehearsal.json")
               for f in files)
    assert "rehearsal_gate" in scope_reduce.scope_names()["scopes"]
    assert "rehearsal_matmul" in trace_reduce.kernel_names()["classes"]
