"""Wall-clock and throughput timers.

TPU-native counterpart of reference utils/timer.py: the reference's
``SynchronizedWallClockTimer`` brackets intervals with ``cuda.synchronize``
(utils/timer.py:26-80). JAX has no such call: a jitted function returns
when its work is ENQUEUED, and ``jax.effects_barrier()`` waits only for
programs with side effects (callbacks, ``io_callback``), which a train step
is not. So an interval here is host wall-clock, and it times the device
only where it is told what to wait for: ``stop(wait_for=arrays)`` blocks
on those arrays (the opt-in ``wall_clock_breakdown`` path of the training
engine does), and an interval stopped without them times the dispatch.
The profiler's trace (telemetry/tracing.py spans beside the device's
operations) is where a step's device time is read.
"""

import time

from deepspeed_tpu.utils.logging import logger


def _device_synchronize(wait_for=None):
    """Block until ``wait_for`` (any pytree of arrays) is computed, and
    until every EFFECTFUL program dispatched so far has run
    (``jax.effects_barrier``: host callbacks, the offload gradient stream).
    It does NOT drain pure programs that nobody waits for: without
    ``wait_for`` a caller times the enqueue of those."""
    try:
        import jax

        if wait_for is not None:
            jax.block_until_ready(wait_for)
        jax.effects_barrier()
    except ImportError:
        pass


class _Interval:
    """One named accumulating interval of host wall-clock. ``stop(
    wait_for=arrays)`` first blocks on the arrays the bracketed work
    produced, so the interval covers the device's part; stopped without
    them it times the dispatch (see the module docstring). elapsed() reads
    the accumulated seconds without disturbing a running interval.

    ``histogram`` (optional) is a telemetry sink with an ``observe(v)``
    method — every completed start/stop interval is observed into it, so
    a registry-backed timer gets p50/p99 per phase for free."""

    __slots__ = ("name", "_acc", "_t0", "histogram")

    def __init__(self, name, histogram=None):
        self.name = name
        self._acc = 0.0
        self._t0 = None  # None <=> not running
        self.histogram = histogram

    @property
    def running(self):
        return self._t0 is not None

    def start(self):
        if self._t0 is not None:
            raise RuntimeError("timer {!r} already started".format(self.name))
        _device_synchronize()
        self._t0 = time.time()

    def stop(self, reset=False, wait_for=None):
        if self._t0 is None:
            raise RuntimeError("timer {!r} not started".format(self.name))
        _device_synchronize(wait_for)
        dt = time.time() - self._t0
        self._acc = dt if reset else self._acc + dt
        self._t0 = None
        if self.histogram is not None:
            self.histogram.observe(dt)

    def reset(self):
        self._acc = 0.0
        self._t0 = None

    def elapsed(self, reset=True):
        """Read accumulated seconds (including the in-flight portion of
        a RUNNING interval) WITHOUT stopping it: the read is a pure
        peek — no device barrier, no stop/start churn, and the running
        interval keeps accumulating as if never observed. ``reset=True``
        zeroes the accumulator and restarts the running window at now
        (the windowed-snapshot semantics metrics(reset=True) builds on)."""
        now = time.time()
        out = self._acc
        if self._t0 is not None:
            out += now - self._t0
        if reset:
            self._acc = 0.0
            if self._t0 is not None:
                self._t0 = now
        return out


class SynchronizedWallClockTimer:
    """Dict of named ``_Interval``s; ``timers(name)`` creates on demand
    (the reference's API shape, utils/timer.py:26-80).

    ``registry`` (optional): a telemetry MetricsRegistry — each named
    interval then observes its completed durations into the registry's
    ``timer_seconds`` histogram labeled ``timer=<timer name>``, which is
    how the training/serving phase timers surface in Prometheus and
    TensorBoard without a second timing layer."""

    Timer = _Interval  # back-compat alias for direct construction

    def __init__(self, registry=None):
        self.timers = {}
        self.registry = registry

    def __call__(self, name):
        t = self.timers.get(name)
        if t is None:
            hist = None
            if self.registry is not None:
                hist = self.registry.histogram("timer_seconds", timer=name)
            t = self.timers[name] = _Interval(name, histogram=hist)
        return t

    @staticmethod
    def memory_usage():
        try:
            import jax

            stats = jax.local_devices()[0].memory_stats() or {}
            gib = 1024.0 ** 3
            return "MA {:.2f} GB  Max_MA {:.2f} GB".format(
                stats.get("bytes_in_use", 0) / gib,
                stats.get("peak_bytes_in_use", 0) / gib)
        except Exception:
            return "MA n/a"

    def log(self, names, normalizer=1.0, reset=True, memory_breakdown=False):
        """One log line of per-name elapsed ms / ``normalizer``."""
        if normalizer <= 0.0:
            raise ValueError("normalizer must be positive")
        parts = ["{}: {:.2f}".format(
            n, self.timers[n].elapsed(reset=reset) * 1000.0 / normalizer)
            for n in names if n in self.timers]
        line = " | ".join(["time (ms)"] + parts)
        if memory_breakdown:
            line += " | " + self.memory_usage()
        logger.info(line)


class ThroughputTimer:
    """Samples/sec every ``steps_per_output`` steps (reference
    timer.py:86-183). The first ``start_step`` steps are warmup
    (compile + cache churn) and are excluded from the average.

    It syncs nothing (it is on in every ``train_batch``): a step's time is
    the LOOP's period, from the first timed ``start()`` to the latest
    ``stop()``, over the timed steps. The host may run ahead of the device
    by the few steps its queue holds; over a run that constant washes out,
    and a loop that reads its loss has none. Timing each start-to-stop
    bracket instead would time the enqueue."""

    def __init__(self, batch_size, num_workers, start_step=2,
                 steps_per_output=50, monitor_memory=False,
                 logging_fn=None, registry=None):
        self.batch_size = batch_size or 1
        self.num_workers = num_workers
        self.start_step = start_step
        self.steps_per_output = steps_per_output
        self.monitor_memory = monitor_memory
        self.logging = logging_fn or logger.info
        # Telemetry: a live samples/sec gauge when a registry is given
        # (reads avg_samples_per_sec at scrape time, -inf clamped to 0).
        if registry is not None:
            registry.gauge("samples_per_sec").set_fn(
                lambda: max(self.avg_samples_per_sec(), 0.0))
        self.epoch_count = 0
        self.local_step_count = 0
        self.total_step_count = 0
        self.total_elapsed_time = 0.0
        self._timed_since = None   # start() of the first timed step
        self._running = False

    def update_epoch_count(self):
        self.epoch_count += 1
        self.local_step_count = 0

    def start(self):
        self._running = True
        if self._timed_since is None and \
                self.total_step_count >= self.start_step:
            self._timed_since = time.time()  # warmup steps are not timed

    def stop(self, report_speed=True):
        if not self._running:
            return
        self._running = False
        timed = self._timed_since is not None
        if timed:
            self.total_elapsed_time = time.time() - self._timed_since
        self.total_step_count += 1
        self.local_step_count += 1
        if (timed and report_speed
                and self.local_step_count % self.steps_per_output == 0):
            self.logging("{}/{}, SamplesPerSec={}".format(
                self.epoch_count, self.local_step_count,
                self.avg_samples_per_sec()))

    def avg_samples_per_sec(self):
        timed_steps = self.total_step_count - self.start_step
        if timed_steps <= 0 or self.total_elapsed_time <= 0:
            return float("-inf")
        per_step = self.total_elapsed_time / timed_steps
        return self.batch_size * self.num_workers / per_step
