"""The program's own record of its start-up (docs/OBSERVABILITY.md, "Why did
this replica take 80 s to come up?"): one recorder at process scope, one set
of ``jax.monitoring`` listeners, every compile by name with its three parts,
the engines' phases, and what ``engine.metrics()["startup"]`` reads back.

The process recorder is the PROCESS's: other tests of this worker have
written to it, so every case cuts at the events it made itself.
"""

import importlib
import json
import logging
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as deepspeed
from deepspeed_tpu import telemetry
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
from deepspeed_tpu.telemetry import (
    MetricsRegistry,
    NullRecorder,
    RecompileDetector,
    SpanRecorder,
    process_recorder,
    startup_summary,
    validate_trace,
)
from deepspeed_tpu.telemetry import instrumentation
from tests.unit.test_chunked_prefill import (
    engine_of,
    make_model,
    prompts_of,
)

SUMMARY_KEYS = {"import_s", "engine_init_s", "trace_s", "lower_s",
                "compile_s", "first_step_s", "ready_s", "programs",
                "cache_misses", "slowest"}


def _since(mark):
    """The process recorder's events written after ``mark`` (its exact
    event count then)."""
    rec = process_recorder()
    new = sum(rec.span_counts().values()) - mark
    return rec.events()[-new:] if new else []


def _mark():
    return sum(process_recorder().span_counts().values())


def _named(events, name):
    return [ev for ev in events if ev["name"] == name]


# ------------------------------------------------------------ the recorder


def test_one_recorder_a_process_never_null_and_sized_for_a_start_up():
    rec = process_recorder()
    assert rec is process_recorder() is telemetry.process_recorder()
    assert isinstance(rec, SpanRecorder) and not isinstance(rec, NullRecorder)
    assert rec.capacity == 65536  # GPT-2 XL's dp4 step alone: 18 thousand


def test_span_seconds_stay_exact_after_the_ring_wrapped():
    ticks = iter(range(1000))
    rec = SpanRecorder(capacity=4, clock=lambda: float(next(ticks)))
    for i in range(10):
        rec.span("compile/trace", start=100.0 + i, end=100.5 + i)
    rec.span("compile/lower", start=0.0, end=2.0)
    rec.span("backwards", start=5.0, end=4.0)  # clamped, as ``dur`` is
    assert len(rec.events()) == 4 and rec.dropped == 8
    assert rec.span_counts() == {"compile/trace": 10, "compile/lower": 1,
                                 "backwards": 1}
    assert rec.span_seconds() == {"compile/trace": 5.0, "compile/lower": 2.0,
                                  "backwards": 0.0}
    with rec.timed("phase"):
        pass
    assert rec.span_seconds()["phase"] == 1.0  # the fake clock's one tick
    assert NullRecorder().span_seconds() == {}


def test_epoch_is_stated_on_both_clocks():
    wall, perf = time.time(), time.perf_counter()
    rec = SpanRecorder()
    wall2, perf2 = time.time(), time.perf_counter()
    assert wall <= rec.epoch <= wall2
    assert perf <= rec.epoch_perf <= perf2
    # One conversion, exact: a moment on one clock lands on the other.
    now_wall, now_perf = time.time(), time.perf_counter()
    assert abs((rec.epoch_perf + (now_wall - rec.epoch)) - now_perf) < 0.05
    assert NullRecorder.epoch_perf == 0.0


# ----------------------------------------------------------- the listeners


def _ours(listeners):
    return [fn for fn in listeners
            if getattr(fn, "__module__", "") == instrumentation.__name__]


def test_listeners_are_installed_once_whatever_is_built_or_imported():
    from jax._src import monitoring

    def installed():
        return (len(_ours(monitoring.get_event_time_span_listeners())),
                len(_ours(monitoring.get_event_duration_listeners())),
                len(_ours(monitoring.get_event_listeners())))

    assert installed() == (1, 1, 1)
    assert instrumentation.install_compile_listeners() is True
    importlib.import_module("deepspeed_tpu")
    cfg, model, params = make_model()
    engine_of(model, params).close()
    engine_of(model, params).close()
    assert installed() == (1, 1, 1)


def test_a_jitted_function_compiles_once_by_name_with_its_three_parts():
    def startup_probe_fn(x):
        return x * 3 + 1

    f = jax.jit(startup_probe_fn)
    x = jnp.ones((5,), jnp.float32)
    jax.block_until_ready(x)
    mark = _mark()
    f(x)
    first = [ev for ev in _since(mark) if instrumentation.program_of(
        ev["args"].get("fun_name")) == "startup_probe_fn"]
    assert sorted(ev["name"] for ev in first) == [
        "compile/backend", "compile/lower", "compile/trace"]
    by = {ev["name"]: ev for ev in first}
    assert by["compile/trace"]["args"]["fun_name"] == "startup_probe_fn"
    assert by["compile/lower"]["args"]["fun_name"] == \
        "jit(startup_probe_fn)"
    assert all(ev["ph"] == "X" and ev["dur"] > 0 for ev in first)
    # trace, then lowering, then the backend: in time, not only in the ring
    assert by["compile/trace"]["ts"] <= by["compile/lower"]["ts"] \
        <= by["compile/backend"]["ts"]
    # the tests keep the persistent cache off: it was not asked
    assert "cache_hit" not in by["compile/backend"]["args"]
    mark = _mark()
    f(x)
    assert _since(mark) == []
    cost = instrumentation.compile_seconds("startup_probe_fn")
    assert set(cost) == {"trace", "lower", "backend"}
    assert cost["backend"] == by["compile/backend"]["dur"] / 1e6


def test_the_cache_events_land_on_the_backend_span_that_ends_next():
    reg = MetricsRegistry()
    instrumentation.count_compiles_into(reg)
    # an hour ago: out of the way of every other case's cut by time
    t0 = time.time() - 3600.0
    mark = _mark()
    instrumentation._on_event("/jax/compilation_cache/cache_hits")
    instrumentation._on_duration(
        "/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
    instrumentation._on_duration("/jax/other", 9.0)
    instrumentation._on_time_span(
        "/jax/core/compile/jaxpr_trace_duration", t0, t0 + 1.0,
        fun_name="hand")
    instrumentation._on_time_span(
        "/jax/core/compile/backend_compile_duration", t0 + 1.0, t0 + 1.5,
        fun_name="jit(hand)")
    instrumentation._on_event("/jax/compilation_cache/cache_misses")
    instrumentation._on_time_span(
        "/jax/core/compile/backend_compile_duration", t0 + 2.0, t0 + 4.0,
        fun_name="jit(hand2)")
    instrumentation._on_time_span(
        "/jax/core/compile/backend_compile_duration", t0 + 4.0, t0 + 4.5,
        fun_name="jit(hand3)")
    instrumentation._on_time_span("/jax/unknown", t0, t0 + 1.0)
    trace, hit, miss, unasked = _since(mark)
    assert trace["name"] == "compile/trace" and trace["args"] == {
        "fun_name": "hand"}
    assert hit["args"] == {"fun_name": "jit(hand)", "cache_hit": True,
                           "retrieval_s": 0.25}
    assert miss["args"] == {"fun_name": "jit(hand2)", "cache_hit": False}
    assert unasked["args"] == {"fun_name": "jit(hand3)"}
    snap = reg.snapshot()
    assert snap["programs_compiled"] == 3
    assert snap["programs_cache_missed"] == 1
    assert snap["compile_trace_seconds"] == pytest.approx(1.0)
    assert snap["compile_lower_seconds"] == 0
    assert snap["compile_backend_seconds"] == pytest.approx(3.0)


def test_an_inner_jit_is_not_counted_twice_union_and_self_time():
    # outer [0, 10] holds inner [2, 5] which holds leaf [3, 4]; a sibling
    # [6, 7]; a separate program [20, 22] overlapped by [21, 23].
    spans = [(0.0, 10.0), (2.0, 5.0), (3.0, 4.0), (6.0, 7.0), (20.0, 22.0),
             (21.0, 23.0)]
    assert instrumentation._union_length(spans) == 13.0
    assert sum(e - s for s, e in spans) == 19.0  # what a plain sum says
    assert instrumentation._self_lengths(spans) == [
        6.0, 2.0, 1.0, 1.0, 2.0, 2.0]
    # order given, not time order
    assert instrumentation._self_lengths([(3.0, 4.0), (0.0, 10.0)]) == [
        1.0, 9.0]
    assert instrumentation._union_length([]) == 0.0

    def inner(x):
        return jnp.sin(x) * 2

    jitted_inner = jax.jit(inner)

    @jax.jit
    def outer_probe(x):
        return jitted_inner(x) + 1

    x = jnp.ones((7,))
    jax.block_until_ready(x)
    mark = _mark()
    outer_probe(x)
    traces = _named(_since(mark), "compile/trace")
    names = [ev["args"]["fun_name"] for ev in traces]
    assert "inner" in names and "outer_probe" in names
    outer = traces[names.index("outer_probe")]
    nested = traces[names.index("inner")]
    assert outer["ts"] <= nested["ts"] and \
        nested["ts"] + nested["dur"] <= outer["ts"] + outer["dur"]
    whole = instrumentation._union_length(
        [(ev["ts"], ev["ts"] + ev["dur"]) for ev in traces])
    assert whole == pytest.approx(outer["dur"])
    assert whole < sum(ev["dur"] for ev in traces)


def test_a_recompile_after_warm_up_names_the_seconds_it_cost(caplog):
    def startup_regrown(x):
        return x + 2

    f = jax.jit(startup_regrown)
    det = RecompileDetector(MetricsRegistry())
    det.watch("startup_regrown", f)
    f(jnp.zeros((3,)))
    det.mark_warm()
    f(jnp.zeros((6,)))
    with caplog.at_level(logging.WARNING, logger="DeepSpeedTPU"):
        from deepspeed_tpu.utils.logging import logger

        logger.addHandler(caplog.handler)
        try:
            assert det.observe() == 1
        finally:
            logger.removeHandler(caplog.handler)
    text = "\n".join(r.getMessage() for r in caplog.records)
    assert "'startup_regrown' recompiled" in text
    cost = instrumentation.compile_seconds("startup_regrown")
    assert "it cost {:.3f} s (trace {:.3f}, lower {:.3f}, backend {:.3f})" \
        .format(sum(cost.values()), cost["trace"], cost["lower"],
                cost["backend"]) in text
    assert sum(cost.values()) > 0


# -------------------------------------------------------------- the engines


def _phases(events):
    return [ev["name"] for ev in events
            if ev["name"].startswith(("setup/", "engine/"))]


@pytest.fixture(scope="module")
def served():
    """One tiny engine's whole life, and what the process recorder saw."""
    cfg, model, params = make_model()
    mark = _mark()
    eng = engine_of(model, params)
    assert eng.step() == []  # nothing to do: not a first step
    eng.submit(prompts_of(cfg, [6])[0], max_new_tokens=9)
    eng.step()
    after_first = _phases(_since(mark))
    eng.run()
    metrics = eng.metrics()
    prometheus = eng.prometheus()
    eng.close()
    return eng, _since(mark), after_first, metrics, prometheus


def test_an_inference_engine_leaves_its_phases_once_each(served):
    eng, events, after_first, _, _ = served
    phases = _phases(events)
    assert phases == ["setup/pool", "setup/params", "setup/engine_init",
                      "setup/first_step", "setup/ready", "engine/closed"]
    assert after_first == phases[:5]  # ready is not said again by a step
    # a float32 model served in float32: nothing for the constructor to cast
    assert _named(events, "setup/params")[0]["args"] == {
        "cast_leaves": 0, "cast_bytes": 0}
    init, first = (_named(events, n)[0] for n in (
        "setup/engine_init", "setup/first_step"))
    assert init["args"] == first["args"] == {"engine": "inference"}
    pool = _named(events, "setup/pool")[0]
    assert init["ts"] <= pool["ts"] and \
        pool["ts"] + pool["dur"] <= init["ts"] + init["dur"]
    ready = _named(events, "setup/ready")[0]
    assert ready["ph"] == "i" and ready["args"]["engine"] == "inference"
    assert ready["args"]["since_import_s"] > 0
    # the one program's three parts fall inside the first step, by time
    mine = [ev for ev in events if instrumentation.program_of(
        ev["args"].get("fun_name")) == "mixed_step"]
    assert sorted(ev["name"] for ev in mine) == [
        "compile/backend", "compile/lower", "compile/trace"]
    for ev in mine:
        assert first["ts"] <= ev["ts"] and \
            ev["ts"] + ev["dur"] <= first["ts"] + first["dur"]
    # the first step is recorded after the fact, where the engine turns
    # ready: no span object rides the stack the one program is traced on
    assert 0 <= ready["ts"] - (first["ts"] + first["dur"]) < 1e5
    assert eng.tracer.span_counts().get("setup/first_step") is None


def test_metrics_startup_has_every_key_and_prometheus_the_series(served):
    _, _, _, metrics, prometheus = served
    startup = metrics["startup"]
    assert set(startup) == SUMMARY_KEYS
    json.dumps(startup)
    assert startup["ready_s"] >= startup["engine_init_s"] > 0
    # exact after the ring wrapped, as it has in a worker that ran for long
    assert startup["ready_s"] >= startup["import_s"] > 0
    assert startup["import_s"] == \
        process_recorder().span_seconds()["setup/import"]
    assert startup["first_step_s"] > 0
    assert startup["trace_s"] > 0 and startup["lower_s"] > 0 \
        and startup["compile_s"] > 0
    assert startup["programs"] >= 1 and startup["cache_misses"] == 0
    assert 1 <= len(startup["slowest"]) <= 5
    names = [row[0] for row in startup["slowest"]]
    assert "mixed_step" in names
    for name, trace_s, lower_s, backend_s, cache_hit in startup["slowest"]:
        assert isinstance(name, str) and cache_hit in (None, True, False)
        assert min(trace_s, lower_s, backend_s) >= 0
    for phase in ("import", "engine_init", "trace", "lower", "compile",
                  "first_step", "ready"):
        assert 'ds_tpu_startup_seconds{{engine="inference",phase="{}"}}' \
            .format(phase) in prometheus
    for counter in ("programs_compiled", "programs_cache_missed",
                    "compile_trace_seconds", "compile_lower_seconds",
                    "compile_backend_seconds"):
        assert "ds_tpu_{}_total".format(counter) in prometheus
    compiled = [line for line in prometheus.splitlines() if line.startswith(
        "ds_tpu_programs_compiled_total")]
    assert float(compiled[0].rsplit(" ", 1)[1]) >= 1  # mixed_step at least


def test_write_trace_carries_the_start_up_under_a_pid_of_its_own(tmp_path):
    cfg, model, params = make_model()
    eng = engine_of(model, params)
    eng.generate(prompts_of(cfg, [5]), max_new_tokens=3)
    doc = json.load(open(eng.write_trace(str(tmp_path / "t.json"))))
    eng.close()
    assert validate_trace(doc) > 0
    pids = {ev["args"]["name"]: ev["pid"] for ev in doc["traceEvents"]
            if ev["ph"] == "M"}
    assert set(pids) == {"engine", "process"}
    assert pids["engine"] != pids["process"]
    by_pid = {}
    for ev in doc["traceEvents"]:
        if ev["ph"] != "M":
            by_pid.setdefault(ev["pid"], set()).add(ev["name"])
    # (``setup/import`` too, in a process young enough to still hold it)
    assert {"setup/engine_init", "setup/first_step", "compile/backend"} <= \
        by_pid[pids["process"]]
    assert "inference/mixed_step" in by_pid[pids["engine"]]
    assert not any(n.startswith(("setup/", "compile/"))
                   for n in by_pid[pids["engine"]])


def test_telemetry_off_still_records_at_process_scope():
    cfg, model, params = make_model()
    mark = _mark()
    eng = engine_of(model, params, telemetry=False)
    eng.generate(prompts_of(cfg, [5]), max_new_tokens=3)
    eng.close()
    assert isinstance(eng.tracer, NullRecorder)
    assert eng.tracer.events() == [] and eng.tracer.span_counts() == {}
    assert _phases(_since(mark)) == [
        "setup/pool", "setup/params", "setup/engine_init", "setup/first_step",
        "setup/ready", "engine/closed"]
    assert set(eng.metrics()["startup"]) == SUMMARY_KEYS


def _tiny_training_engine():
    cfg = GPT2Config.tiny()
    engine, _, _, _ = deepspeed.initialize(
        model=GPT2LMHeadModel(cfg),
        config_params={"train_batch_size": 8,
                       "optimizer": {"type": "AdamW",
                                     "params": {"lr": 1e-3}},
                       "bf16": {"enabled": True}})
    ids = np.random.RandomState(0).randint(0, cfg.vocab_size, size=(8, 16))
    return engine, ids


def test_a_training_engine_leaves_its_phases_once_each():
    mark = _mark()
    engine, ids = _tiny_training_engine()
    built = _phases(_since(mark))
    assert built[-1] == "setup/engine_init"
    assert set(built[:-1]) == {"setup/params", "setup/optimizer_state"}
    for _ in range(3):
        engine.train_batch(batch=(ids, ids))
    events = _since(mark)
    phases = _phases(events)[len(built):]
    # the lazy init places the state, the fused step is built, traced,
    # compiled and run once; then ready, and nothing more on later steps
    assert phases[-3:] == ["setup/programs", "setup/first_step",
                           "setup/ready"]
    assert set(phases[:-3]) <= {"setup/params", "setup/optimizer_state"}
    first = _named(events, "setup/first_step")[0]
    assert first["args"] == {"engine": "training"}
    step = [ev for ev in events if instrumentation.program_of(
        ev["args"].get("fun_name")) == "train_step"]
    assert sorted(ev["name"] for ev in step) == [
        "compile/backend", "compile/lower", "compile/trace"]
    for ev in step:
        assert first["ts"] <= ev["ts"] and \
            ev["ts"] + ev["dur"] <= first["ts"] + first["dur"]
    summary = startup_summary()
    assert set(summary) == SUMMARY_KEYS
    assert summary["ready_s"] >= summary["engine_init_s"] > 0
    assert "train_step" in [row[0] for row in summary["slowest"]]
    assert engine.tracer.span_counts().get("setup/first_step") is None
    assert engine.telemetry.snapshot()["programs_compiled"] >= 1


def test_the_three_call_path_is_ready_after_its_first_step():
    mark = _mark()
    engine, ids = _tiny_training_engine()
    loss = engine(ids, ids)
    # begun with the first forward; recorded, after the fact, with ready
    assert engine._startup == 1
    assert not {"setup/first_step", "setup/ready"} & set(
        _phases(_since(mark)))
    engine.backward(loss)
    engine.step()
    loss = engine(ids, ids)
    engine.backward(loss)
    engine.step()
    phases = _phases(_since(mark))
    assert phases.count("setup/first_step") == 1
    assert phases.count("setup/ready") == 1
    assert phases.index("setup/first_step") < phases.index("setup/ready")


# --------------------------------------------------------------- the import


def test_the_package_records_its_import_and_telemetry_imports_without_jax():
    code = (
        "import sys, json\n"
        "import deepspeed_tpu.telemetry as t\n"
        "import deepspeed_tpu\n"
        "assert t.install_compile_listeners() is True\n"
        "from jax._src import monitoring\n"
        "rec = t.process_recorder()\n"
        "ev = rec.events()[0]\n"
        "print(json.dumps({'first': ev['name'], 'dur': ev['dur'],\n"
        "  'counts': rec.span_counts(),\n"
        "  'spans': len(monitoring.get_event_time_span_listeners())}))\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300,
                         env=dict(__import__("os").environ,
                                  JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr
    said = json.loads(out.stdout.strip().splitlines()[-1])
    assert said["first"] == "setup/import" and said["dur"] > 0
    assert said["counts"]["setup/import"] == 1
    assert said["spans"] == 1
    # ... and the telemetry package alone, with jax out of reach, still
    # imports: the listeners are the package's, installed by its last line.
    alone = (
        "sys.modules['jax'] = None\n"
        "from deepspeed_tpu.telemetry import instrumentation as i\n"
        "print(i.install_compile_listeners(), i.startup_summary()['programs'])\n")
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys, types\n"
         "pkg = types.ModuleType('deepspeed_tpu')\n"
         "pkg.__path__ = [{!r}]\n"
         "sys.modules['deepspeed_tpu'] = pkg\n".format(
             deepspeed.__path__[0]) + alone],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == "False 0"
