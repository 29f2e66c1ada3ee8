"""``setup_s`` gets per-layer metrics (PR 53): the eight ``setup_*`` readers over
``benchmark/setup_reduce.py``, which cuts the program's own record of its
start-up (``deepspeed_tpu.telemetry.process_recorder()``) at the moment the
measured window opened. The arithmetic on hand-built event lists, the readers
on a program without a recorder, the manifest's rows by NAME, and one traced
CPU stand-in that reports all eight.
"""

import json

import pytest

from benchmark import costs, harness, setup_reduce
from tests.benchmark import tiny

NAMES = ("setup_boot_s", "setup_engine_init_s", "setup_trace_s",
         "setup_lower_s", "setup_compile_s", "setup_warm_s",
         "setup_programs", "setup_cache_misses")
PART = {"setup_boot_s": "boot_s", "setup_engine_init_s": "engine_init_s",
        "setup_trace_s": "trace_s", "setup_lower_s": "lower_s",
        "setup_compile_s": "compile_s", "setup_warm_s": "warm_s",
        "setup_programs": "programs", "setup_cache_misses": "cache_misses"}
CELLS = ["train-gpt2m-1chip", "train-gpt2xl-zero-dp4",
         "serve-gpt2m-decode-closed", "serve-gpt2m-chat-loaded",
         "serve-olmoe-decode-closed", "serve-granite4h-decode-closed",
         "serve-dsv3-decode-closed", "serve-kimilinear-decode-closed",
         "serve-lfm2moe-decode-closed", "serve-jamba2-decode-closed",
         "serve-sdar-diffusion-closed"]


def _program(name, at, trace, lower, backend, hit=None, inner=()):
    """One program's three spans from ``at`` on, back to back; ``inner``:
    ``(name, offset, length)`` traces nested inside its trace."""
    args = {} if hit is None else {"cache_hit": hit}
    out = [("compile/trace", at, at + trace, {"fun_name": name})]
    out += [("compile/trace", at + off, at + off + length, {"fun_name": n})
            for n, off, length in inner]
    out.append(("compile/lower", at + trace, at + trace + lower,
                {"fun_name": "jit({})".format(name)}))
    out.append(("compile/backend", at + trace + lower,
                at + trace + lower + backend,
                dict(args, fun_name="jit({})".format(name))))
    return out


def _a_run(hit, backend=0.5):
    """A serving process on the run's clock: start 100, imports to 103, the
    weights' program, the engine (its pool's program inside), the first step
    (the one program), ready at 120, two tiny programs of the warm-up, the
    window at 130; and the reference's compile AFTER the window."""
    events = [("setup/import", 100.5, 103.0, {})]
    events += _program("init", 104.0, 0.5, 0.25, backend, hit)
    events += _program("zeros", 107.0, 0.125, 0.125, backend / 2, hit)
    events.append(("setup/pool", 106.5, 108.5, {}))
    events.append(("setup/engine_init", 106.0, 109.0,
                   {"engine": "inference"}))
    events += _program("mixed_step", 110.0, 4.0, 2.0, 8 * backend, hit,
                       inner=[("attend", 1.0, 1.0), ("attend", 2.5, 0.5)])
    events.append(("setup/ready", 120.0, 120.0, {"engine": "inference"}))
    events.append(("setup/first_step", 109.5, 120.5,
                   {"engine": "inference"}))
    events += _program("admit", 122.0, 0.25, 0.25, backend / 2, hit)
    events += _program("admit", 124.0, 0.25, 0.25, backend / 2, hit)
    events += _program("reference", 131.0, 5.0, 5.0, 20.0, hit)
    # one that BEGINS before the window and ends after it: not set-up
    events.append(("compile/trace", 129.5, 130.5, {"fun_name": "late"}))
    return events


def _identity(parts, setup_s):
    return (parts["boot_s"] + parts["engine_init_s"] + parts["compiling_s"]
            + parts["unnamed_s"] + parts["warm_s"]) - setup_s


def test_a_warm_run_is_cut_into_parts_that_add_up_to_the_millisecond():
    parts = setup_reduce.reduce_setup(_a_run(hit=True), 100.0, 130.0)
    assert parts["boot_s"] == 4.0 and parts["import_s"] == 2.5
    # unions: the nested traces of ``attend`` lie inside ``mixed_step``'s
    assert parts["trace_s"] == 0.5 + 0.125 + 4.0 + 0.25 + 0.25
    assert parts["lower_s"] == 0.25 + 0.125 + 2.0 + 0.25 + 0.25
    assert parts["compile_s"] == 0.5 + 0.25 + 4.0 + 0.25 + 0.25
    assert parts["compiling_s"] == parts["trace_s"] + parts["lower_s"] \
        + parts["compile_s"]
    # the constructor less the pool's program compiled inside it
    assert parts["engine_init_s"] == 3.0 - 0.5
    # ready to the window less the two tiny programs of the warm-up
    assert parts["warm_s"] == 10.0 - 2 * 0.75
    assert parts["ready_to_window_s"] == 10.0
    assert parts["to_ready_s"] == 20.0 and parts["first_step_s"] == 11.0
    assert parts["programs"] == 5 and parts["cache_misses"] == 0
    assert abs(_identity(parts, 30.0)) < 1e-3
    # what no span names: between the weights' program and the engine, the
    # step's run after its compile, before the first tiny program
    assert parts["unnamed_s"] == pytest.approx(
        30.0 - 4.0 - 2.5 - parts["compiling_s"] - 8.5)
    # the table is SELF time: mixed_step's trace less the 1.5 s inside it
    table = {row[0]: row[1:] for row in parts["slowest"]}
    assert table["mixed_step"] == [2.5, 2.0, 4.0, True]
    assert table["attend"] == [1.5, 0.0, 0.0, None]
    assert table["admit"] == [0.5, 0.5, 0.5, True]
    assert "reference" not in table and "late" not in table
    assert parts["slowest"][0][0] == "mixed_step"
    json.dumps(parts)


def test_a_cold_run_differs_in_the_backend_and_says_so():
    warm = setup_reduce.reduce_setup(_a_run(hit=True), 100.0, 130.0)
    cold = setup_reduce.reduce_setup(_a_run(hit=False, backend=0.75), 100.0,
                                     130.0)
    assert cold["cache_misses"] == cold["programs"] == 5
    assert cold["compile_s"] == 1.5 * warm["compile_s"]
    assert cold["trace_s"] == warm["trace_s"]
    assert cold["lower_s"] == warm["lower_s"]
    assert abs(_identity(cold, 30.0)) < 1e-3
    # a program the cache was not asked about counts as no miss
    unasked = setup_reduce.reduce_setup(_a_run(hit=None), 100.0, 130.0)
    assert unasked["programs"] == 5 and unasked["cache_misses"] == 0


def test_events_that_end_after_the_window_are_not_set_up():
    early = setup_reduce.reduce_setup(_a_run(hit=True), 100.0, 121.0)
    # the window opened before the warm-up's tiny programs: they are gone
    assert early["programs"] == 3 and early["warm_s"] == 1.0
    assert abs(_identity(early, 21.0)) < 1e-3
    # ... and before the engine was ready: no warm-up, the first step's
    # compile (ends at 120) does not count, nor does the span itself
    earlier = setup_reduce.reduce_setup(_a_run(hit=True), 100.0, 115.0)
    assert earlier["warm_s"] == 0.0 and earlier["to_ready_s"] is None
    assert earlier["first_step_s"] is None
    assert earlier["programs"] == 2
    assert earlier["trace_s"] == 0.5 + 0.125 + 4.0 and \
        earlier["lower_s"] == 0.25 + 0.125
    assert abs(_identity(earlier, 15.0)) < 1e-3
    # nothing at all before the window: everything is boot
    nothing = setup_reduce.reduce_setup([], 100.0, 130.0)
    assert nothing["boot_s"] == 30.0 and nothing["unnamed_s"] == 0.0
    assert nothing["programs"] == 0 and nothing["slowest"] == []


def test_many_engines_a_process_cut_at_the_last_one_closed():
    first = [(n, s - 100.0, e - 100.0, a) for n, s, e, a in _a_run(True)
             if e <= 130.0]
    first.append(("engine/closed", 35.0, 35.0, {"engine": "inference"}))
    events = first + [ev for ev in _a_run(True)]
    # a test process imported the harness long before this run
    assert setup_reduce.run_start(events, process_start=-50.0) == 35.0
    # run.py's process: nothing closed before the one engine was built
    assert setup_reduce.run_start(_a_run(True), process_start=100.0) == 100.0
    # an engine closed AFTER the newest was built does not move the start
    late = _a_run(True) + [("engine/closed", 140.0, 140.0, {})]
    assert setup_reduce.run_start(late, process_start=100.0) == 100.0
    assert setup_reduce.run_start([], process_start=7.0) == 7.0
    parts = setup_reduce.reduce_setup(events, 35.0, 130.0)
    alone = setup_reduce.reduce_setup(_a_run(True), 100.0, 130.0)
    # the earlier engine's programs, phases and ready are not this run's
    for key in ("trace_s", "lower_s", "compile_s", "engine_init_s",
                "warm_s", "programs", "slowest", "first_step_s"):
        assert parts[key] == alone[key], key
    assert parts["boot_s"] == alone["boot_s"] + 65.0
    assert abs(_identity(parts, 95.0)) < 1e-3


class _Recorder(object):
    epoch_perf = 1000.0
    dropped = 0

    def events(self):
        return [
            {"name": "setup/engine_init", "ph": "X", "ts": 2e6, "dur": 1e6,
             "pid": 0, "tid": 0, "args": {"engine": "training"}},
            {"name": "compile/backend", "ph": "X", "ts": 4e6, "dur": 5e5,
             "pid": 0, "tid": 0, "args": {"fun_name": "jit(train_step)",
                                          "cache_hit": False}},
            {"name": "setup/ready", "ph": "i", "s": "t", "ts": 5e6, "pid": 0,
             "tid": 0, "args": {"engine": "training"}}]


def test_the_readers_read_one_reduction_and_note_it_once(monkeypatch, capsys):
    monkeypatch.setattr(setup_reduce, "recorder", _Recorder)
    monkeypatch.setattr(harness, "_T0", 1001.0)
    assert setup_reduce.on_run_clock(_Recorder())[2] == (
        "setup/ready", 1005.0, 1005.0, {"engine": "training"})
    run = {"values": {"setup_s": 6.0}}
    got = {name: harness.load_by_name("layer_metrics", name).read(run)
           for name in NAMES}
    assert got == {"setup_boot_s": 1.0, "setup_engine_init_s": 1.0,
                   "setup_trace_s": 0.0, "setup_lower_s": 0.0,
                   "setup_compile_s": 0.5, "setup_warm_s": 2.0,
                   "setup_programs": 1, "setup_cache_misses": 1}
    notes = [json.loads(line) for line in capsys.readouterr().out.splitlines()
             if '"event": "setup_phases"' in line]
    assert len(notes) == 1
    note = notes[0]
    assert note["setup_s"] == 6.0 and note["unnamed_s"] == 1.5
    assert note["to_ready_s"] == 4.0 and note["dropped"] == 0
    assert note["slowest"] == [["train_step", 0.0, 0.0, 0.5, False]]
    for key in ("import_s", "first_step_s", "compiling_s", "events", "t"):
        assert key in note


def test_on_the_parents_vocabulary_every_reader_returns_nothing(monkeypatch,
                                                               capsys):
    # a program without ``process_recorder``: every commit before PR 53
    monkeypatch.setattr(setup_reduce, "recorder", lambda: None)
    run = {"values": {"setup_s": 30.0}}
    for name in NAMES:
        assert harness.load_by_name("layer_metrics", name).read(run) is None
    assert "setup_phases" not in run
    assert "setup_phases" not in capsys.readouterr().out
    # ... and that is what ``recorder`` says where the import fails
    import deepspeed_tpu.telemetry as telemetry

    monkeypatch.undo()
    monkeypatch.delattr(telemetry, "process_recorder")
    assert setup_reduce.recorder() is None


@pytest.mark.parametrize("name", NAMES)
def test_the_manifest_row_by_name(name):
    manifest = harness.load_json(harness.MANIFEST)
    rows = [m for m in manifest["per_layer"] if m["name"] == name]
    assert len(rows) == 1
    counter = name in ("setup_programs", "setup_cache_misses")
    assert rows[0] == {
        "name": name, "unit": "programs" if counter else "s",
        "better": "lower",
        "source": "program_counter" if counter else "program_span",
        "layer": "start-up", "moves": "setup_s", "workloads": CELLS}
    # every cell reports ``setup_s``: the eleven by name, none by default
    assert set(CELLS) <= {w["name"] for w in manifest["workloads"]}
    setup = [m for m in manifest["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]
    assert setup_reduce.reading({"setup_phases": {PART[name]: 7}},
                                PART[name]) == 7


def _without_the_eight():
    manifest = harness.load_json(harness.MANIFEST)
    manifest["per_layer"] = [m for m in manifest["per_layer"]
                             if m["name"] not in NAMES]
    return manifest


def _dsv3(manifest):
    from tests.benchmark import test_deepseek_v3 as pins

    pins.test_the_cell_is_one_chip_with_the_issues_traffic(manifest)
    return pins.CELL


def _kimi(manifest):
    from tests.benchmark import test_kimi_linear as pins

    pins.test_the_cell_is_one_chip_with_dsv3s_traffic_unchanged(manifest)
    return pins.CELL


def _lfm2(manifest):
    from tests.benchmark import test_jamba as pins

    pins.test_the_lfm2_cell_stands_as_pr_44_left_it(
        harness.Cell(manifest, pins.CELL))
    return pins.LFM2_CELL


def _sdar(manifest):
    from tests.benchmark import test_sdar_moe as pins

    pins.test_the_stand_in_is_the_cells(manifest)
    return pins.CELL


@pytest.mark.parametrize("pin", [_dsv3, _kimi, _lfm2, _sdar],
                         ids=lambda pin: pin.__name__.strip("_"))
def test_a_pin_of_a_cells_exact_set_holds_beside_the_eight(pin):
    """Four tests in files this PR may not edit pin a cell's EXACT per-layer
    set as it was before the eight rows (``tests/conftest.py`` marks them):
    every line of each holds on the manifest without the eight, by calling
    the function itself, and the cell's set is that set and the eight."""
    before = _without_the_eight()
    cell = pin(before)
    now = harness.load_json(harness.MANIFEST)
    assert {m["name"] for m in harness.Cell(now, cell).metrics("per_layer")} \
        == {m["name"] for m in harness.Cell(before, cell).metrics(
            "per_layer")} | set(NAMES)
    # the eight are appended: what stood before them stands where it stood
    assert now["per_layer"][:len(before["per_layer"])] == before["per_layer"]
    assert [m["name"] for m in now["per_layer"][len(before["per_layer"]):]] \
        == list(NAMES)


def test_a_traced_stand_in_reports_all_eight(monkeypatch, capsys):
    monkeypatch.setattr(costs, "device_peaks", lambda kind: tiny.CPU_PEAKS)
    from deepspeed_tpu.telemetry import process_recorder

    standin = tiny.standins()["serve-gpt2m-decode-closed"]
    # A test process runs many engines: the run's set-up begins where the
    # last one closed (``setup_reduce.run_start``), which is now.
    process_recorder().instant("engine/closed", engine="the test before")
    result = tiny.check_traced(tiny.manifest(), standin)
    for name in NAMES:
        reading = result["metrics"][name]
        assert reading["value"] >= 0
        assert reading["unit"] == ("programs" if name in (
            "setup_programs", "setup_cache_misses") else "s")
    notes = [json.loads(line) for line in capsys.readouterr().out.splitlines()
             if '"event": "setup_phases"' in line]
    assert len(notes) == 1
    note = notes[0]
    setup_s = note["setup_s"]
    assert setup_s > 0
    assert abs(note["boot_s"] + note["engine_init_s"] + note["compiling_s"]
               + note["unnamed_s"] + note["warm_s"] - setup_s) < 1e-3
    # the tests keep the persistent cache off: no program missed it
    assert result["metrics"]["setup_cache_misses"]["value"] == 0
    # this run built an engine and stepped it inside its set-up
    assert result["metrics"]["setup_engine_init_s"]["value"] > 0
    assert result["metrics"]["setup_programs"]["value"] >= 1
