"""The five readers of a request's way through the prefill lane
(``chat.lane_wait_p50_ms``, ``chat.lane_run_p50_ms``,
``chat.first_token_lag_p50_ms``, ``chat.lane_busy_pct``,
``chat.lane_fill_pct``) on hand-built instants with known answers, and on
the open cell's stand-in, traced on the CPU.

The hand-built tail, in seconds on the profiler's clock, eight steps
dispatched (``inference/mixed_step`` x 8), ``prefill_chunk`` 16:

    request 7: admitted 1.000, slices at 1.010 (16 tokens), 1.040 (16) and
               1.070 (4, the last: ``last_slice`` 1.0701), first token 1.120
    request 8: admitted 1.005, one slice at 1.100 (10 tokens, the last:
               ``last_slice`` 1.1001); no first token inside the tail
    request 9: its SECOND slice at 1.130 (``slices`` 2, 16 tokens, not the
               last), carrying ``lane_wait_ms`` 7.5: admitted, and first
               in the lane, before the tail
    request 5: a chunk at 1.140 carrying all three parts: 3.0, 60.0, 51.5
"""

import contextlib
import io
import os
import types

import pytest

from benchmark import costs, harness, scope_reduce, trace_reduce
from tests.benchmark import tiny

READERS = ("lane_wait_p50_ms", "lane_run_p50_ms", "first_token_lag_p50_ms",
           "lane_busy_pct", "lane_fill_pct")
NAMES = ["chat." + r for r in READERS]


def _slice(rid, t, tokens, slices, **carried):
    return (rid, t, dict(rid=rid, tokens=tokens, slices=slices, **carried))


TAIL = {
    "request/admitted": [(7, 1.000, {"rid": 7}), (8, 1.005, {"rid": 8})],
    "request/slice": [
        _slice(7, 1.010, 16, 1), _slice(7, 1.040, 16, 2),
        _slice(7, 1.070, 4, 3), _slice(8, 1.100, 10, 1),
        _slice(9, 1.130, 16, 2, lane_wait_ms=7.5)],
    "request/last_slice": [(7, 1.0701, {"rid": 7, "slices": 3}),
                           (8, 1.1001, {"rid": 8, "slices": 1})],
    "request/first_token": [(7, 1.120, {"rid": 7})],
    "request/chunk": [(5, 1.140, {"rid": 5, "lane_wait_ms": 3.0,
                                  "lane_run_ms": 60.0,
                                  "first_lag_ms": 51.5})],
}
HOST = {"inference/mixed_step": {"count": 8, "total_s": 0.008,
                                 "self_s": 0.008}}


def _read(monkeypatch, capsys, instants, host=HOST):
    """Each reader's value over a hand-built reduction, and the notes."""
    monkeypatch.setattr(scope_reduce, "of_run",
                        lambda run: {"instants": instants, "host": host})
    cell = types.SimpleNamespace(
        traffic={"engine": {"prefill_chunk": 16}})
    values = {r: harness.load_by_name("layer_metrics", r).read({"cell": cell})
              for r in READERS}
    return values, capsys.readouterr().out


def test_readers_on_a_hand_built_tail(monkeypatch, capsys):
    values, notes = _read(monkeypatch, capsys, TAIL)
    # 7: 10.0 and 8: 95.0 by their instants; 9 and 5 by what they carry
    assert values["lane_wait_p50_ms"] == pytest.approx(
        (7.5 + 10.0) / 2)                        # of 3.0, 7.5, 10.0, 95.0
    # 7: first slice -> last_slice 60.1; 8: 0.1; 5 carried 60.0. Request 9's
    # slice is its second, so it pairs with nothing and carries nothing
    assert values["lane_run_p50_ms"] == pytest.approx(60.0)
    # 7: 49.9 by its instants, 5 carried; 8 has no first token yet
    assert values["first_token_lag_p50_ms"] == pytest.approx(
        (49.9 + 51.5) / 2)
    assert values["lane_busy_pct"] == pytest.approx(100.0 * 5 / 8)
    assert values["lane_fill_pct"] == pytest.approx(
        100.0 * (16 + 16 + 4 + 10 + 16) / (5 * 16))
    assert notes.count('"event": "request_gaps"') == 3
    assert '"samples": 4' in notes and '"slices": 5' in notes


def test_a_tail_whose_lane_stood_idle_reads_zero_load(monkeypatch, capsys):
    """Requests that decode through the tail carry their wait for the lane:
    the program names it, and no slice was dispatched."""
    idle = {"request/chunk": TAIL["request/chunk"]}
    values, _ = _read(monkeypatch, capsys, idle)
    assert values["lane_busy_pct"] == 0.0 and values["lane_fill_pct"] == 0.0
    assert values["lane_wait_p50_ms"] == 3.0
    assert values["lane_run_p50_ms"] == 60.0
    assert values["first_token_lag_p50_ms"] == 51.5


def test_a_program_without_the_instants_reads_nothing(monkeypatch, capsys):
    """The parent's program: ``request/admitted``, ``first_token`` and
    ``chunk`` with ``queue_ms`` / ``prefill_ms`` alone."""
    parent = {
        "request/admitted": [(7, 1.0, {"rid": 7, "queue_ms": 0.04})],
        "request/first_token": [(7, 1.08, {"rid": 7, "queue_ms": 0.04,
                                           "prefill_ms": 80.0})],
        "request/chunk": [(5, 1.14, {"rid": 5, "queue_ms": 0.05,
                                     "prefill_ms": 76.0})]}
    values, _ = _read(monkeypatch, capsys, parent)
    assert values == dict.fromkeys(READERS)
    values, _ = _read(monkeypatch, capsys, {}, {})
    assert values == dict.fromkeys(READERS)


def test_the_manifest_names_the_five_for_the_open_cell():
    rows = {m["name"]: m
            for m in harness.load_json(harness.MANIFEST)["per_layer"]}
    for name in NAMES:
        row = rows[name]
        assert row["workloads"] == ["serve-gpt2m-chat-loaded"]
        assert (row["layer"], row["source"], row["moves"]) == (
            "serving engine", "program_span", "serve_tpot_p50_ms")
        assert row["unit"] == ("%" if name.endswith("_pct") else "ms")
    assert rows["chat.lane_fill_pct"]["better"] == "higher"
    assert all(rows[n]["better"] == "lower" for n in NAMES[:4])


CELL = "serve-tiny-open-phases"


@pytest.fixture(scope="module")
def traced():
    """One traced run of the open cell's stand-in under a name of its own
    (the trace directory is the cell's name, and ``test_harness.py`` traces
    the stand-in beside this file): the result, the notes, the instants."""
    manifest = tiny.manifest()
    for row in manifest["workloads"]:
        if row["name"] == "serve-tiny-open":
            row["name"] = CELL
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = [
                CELL if w == "serve-tiny-open" else w
                for w in metric["workloads"]]
    out = io.StringIO()
    with pytest.MonkeyPatch.context() as patch, \
            contextlib.redirect_stdout(out):
        patch.setattr(costs, "device_peaks", lambda kind: tiny.CPU_PEAKS)
        result = tiny.run(manifest, CELL, trace=1)
    path = trace_reduce.find_xplane(
        os.path.join(harness.OUT_DIR, "trace", CELL))
    return result, out.getvalue(), \
        scope_reduce.reduce_scopes(path, [])["instants"]


def test_the_open_stand_in_reports_all_five(traced):
    result, notes, _ = traced
    assert result["correct"] is True
    got = {n: result["metrics"][n]["value"] for n in NAMES}
    for name in NAMES[:3]:
        assert 0.0 <= got[name] < 60e3, name
    assert 0.0 <= got["chat.lane_busy_pct"] <= 100.0
    assert 0.0 <= got["chat.lane_fill_pct"] <= 100.0
    # the tail is three steps of the tiny mix: a slice, where it holds one,
    # is 4 to 16 tokens of a 16-token lane
    if got["chat.lane_busy_pct"]:
        assert 25.0 <= got["chat.lane_fill_pct"] <= 100.0
    assert notes.count('"event": "request_gaps"') == 5  # two older readers
    assert '"event": "lane_load"' in notes


def test_the_parts_of_the_stand_ins_requests_add_up(traced):
    """Every request the traced tail saw past its first token carries three
    parts that add up to its ``prefill_ms``."""
    whole = 0
    for rows in traced[2].values():
        for _, _, stats in rows:
            if "prefill_ms" in stats:
                whole += 1
                parts = sum(float(stats[k]) for k in (
                    "lane_wait_ms", "lane_run_ms", "first_lag_ms"))
                assert parts == pytest.approx(float(stats["prefill_ms"]),
                                              abs=0.01)
    assert whole > 0
