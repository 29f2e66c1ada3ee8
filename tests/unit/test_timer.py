"""utils/timer.py — interval semantics the engines' phase timing rests on.

The contract under test:
1. START/STOP — double-start and stop-without-start raise; stop
   accumulates (or replaces under ``reset=True``).
2. ELAPSED — ``elapsed(reset=False)`` is a PURE PEEK: it reads the
   accumulator plus the in-flight portion of a running interval without
   stopping it, and the running interval keeps accumulating afterwards.
   ``elapsed(reset=True)`` zeroes the window and restarts a running
   interval at now — the windowed-snapshot building block.
3. REGISTRY — a registry-backed timer observes every completed interval
   into the ``timer_seconds`` histogram labeled ``timer=<name>`` (the
   label key must not collide with the histogram's positional args).
"""

import pytest

from deepspeed_tpu.telemetry import MetricsRegistry
from deepspeed_tpu.utils.timer import (
    SynchronizedWallClockTimer,
    ThroughputTimer,
    _Interval,
)


def test_start_stop_guards():
    t = _Interval("t")
    with pytest.raises(RuntimeError):
        t.stop()
    t.start()
    with pytest.raises(RuntimeError):
        t.start()
    t.stop()
    with pytest.raises(RuntimeError):
        t.stop()


def test_stop_accumulates_and_reset_replaces(monkeypatch):
    clock = [100.0]
    monkeypatch.setattr("deepspeed_tpu.utils.timer.time",
                        type("T", (), {"time": staticmethod(
                            lambda: clock[0])}))
    t = _Interval("t")
    t.start()
    clock[0] += 2.0
    t.stop()
    t.start()
    clock[0] += 3.0
    t.stop()
    assert t.elapsed(reset=False) == pytest.approx(5.0)  # accumulated
    t.start()
    clock[0] += 1.0
    t.stop(reset=True)  # replace, not accumulate
    assert t.elapsed(reset=False) == pytest.approx(1.0)


def test_elapsed_peek_does_not_stop_running_interval(monkeypatch):
    clock = [0.0]
    monkeypatch.setattr("deepspeed_tpu.utils.timer.time",
                        type("T", (), {"time": staticmethod(
                            lambda: clock[0])}))
    t = _Interval("t")
    t.start()
    clock[0] = 2.0
    assert t.elapsed(reset=False) == pytest.approx(2.0)  # in-flight read
    clock[0] = 5.0
    # Still running and still accumulating: the peek didn't stop it.
    assert t.elapsed(reset=False) == pytest.approx(5.0)
    t.stop()
    assert t.elapsed(reset=False) == pytest.approx(5.0)


def test_elapsed_reset_restarts_running_window(monkeypatch):
    clock = [0.0]
    monkeypatch.setattr("deepspeed_tpu.utils.timer.time",
                        type("T", (), {"time": staticmethod(
                            lambda: clock[0])}))
    t = _Interval("t")
    t.start()
    clock[0] = 3.0
    assert t.elapsed(reset=True) == pytest.approx(3.0)
    clock[0] = 4.0
    # New window opened at the reset instant, interval still running.
    assert t.elapsed(reset=False) == pytest.approx(1.0)
    t.stop()
    assert t.elapsed(reset=False) == pytest.approx(1.0)


def test_reset_clears_even_running():
    t = _Interval("t")
    t.start()
    t.reset()
    assert t.elapsed(reset=False) == 0.0
    t.start()  # reset cleared the running flag: start is legal again
    t.stop()


def test_named_timers_create_on_demand_and_log():
    timers = SynchronizedWallClockTimer()
    timers("a").start()
    timers("a").stop()
    assert timers("a") is timers.timers["a"]
    timers.log(["a", "missing"], normalizer=2.0)  # missing names skipped
    with pytest.raises(ValueError):
        timers.log(["a"], normalizer=0.0)


def test_registry_backed_timer_observes_completed_intervals():
    reg = MetricsRegistry(engine="test")
    timers = SynchronizedWallClockTimer(registry=reg)
    for _ in range(3):
        timers("fwd").start()
        timers("fwd").stop()
    h = reg.histogram("timer_seconds", timer="fwd")
    assert h.count == 3
    assert h.labels == {"engine": "test", "timer": "fwd"}
    # A second named timer lands in its own labeled series.
    timers("bwd").start()
    timers("bwd").stop()
    assert reg.histogram("timer_seconds", timer="bwd").count == 1
    assert h.count == 3


def test_throughput_timer_warmup_and_average(monkeypatch):
    # Clock starts nonzero: 0.0 is the timer's warmup sentinel.
    clock = [100.0]
    monkeypatch.setattr("deepspeed_tpu.utils.timer.time",
                        type("T", (), {"time": staticmethod(
                            lambda: clock[0])}))
    reg = MetricsRegistry()
    tt = ThroughputTimer(batch_size=4, num_workers=2, start_step=2,
                         steps_per_output=100, registry=reg)
    assert reg.gauge("samples_per_sec").value == 0.0  # -inf clamped
    for _ in range(2):  # warmup: counted, not timed
        tt.start()
        tt.stop()
    assert tt.avg_samples_per_sec() == float("-inf")
    for _ in range(3):
        tt.start()
        clock[0] += 0.5
        tt.stop()
    # 8 samples per 0.5 s step.
    assert tt.avg_samples_per_sec() == pytest.approx(16.0)
    assert reg.gauge("samples_per_sec").value == pytest.approx(16.0)


def test_throughput_timer_syncs_nothing_and_times_the_loop(monkeypatch):
    """It is on in every train_batch, so it may not wait for the device;
    a step's time is the loop's period, the gap between steps included
    (timing each start-to-stop bracket would time the enqueue)."""
    import deepspeed_tpu.utils.timer as timer_mod

    def no_sync(*_a, **_k):
        raise AssertionError("ThroughputTimer must not synchronize")
    monkeypatch.setattr(timer_mod, "_device_synchronize", no_sync)
    clock = [10.0]
    monkeypatch.setattr("deepspeed_tpu.utils.timer.time",
                        type("T", (), {"time": staticmethod(
                            lambda: clock[0])}))
    tt = ThroughputTimer(batch_size=16, num_workers=1, start_step=1,
                         steps_per_output=100)
    tt.start()
    tt.stop()  # warmup
    for _ in range(4):
        tt.start()
        clock[0] += 0.01   # the dispatch returns at once...
        tt.stop()
        clock[0] += 0.39   # ...and the loop waits for its loss
    # 4 steps from the first timed start to the last stop: 3 x 0.4 + 0.01
    assert tt.total_elapsed_time == pytest.approx(1.21)
    assert tt.avg_samples_per_sec() == pytest.approx(16 * 4 / 1.21)
    tt.stop()  # a stop without a start changes nothing
    assert tt.total_step_count == 5


def test_interval_waits_for_the_arrays_it_is_given(monkeypatch):
    import jax

    waited = []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: waited.append(x) or x)
    t = _Interval("t")
    t.start()
    t.stop()
    assert waited == []  # stopped without arrays: it times the dispatch
    t.start()
    t.stop(wait_for=("loss", "grads"))
    assert waited == [("loss", "grads")]
