"""Per-request trace spans — Chrome trace-event JSON + JSONL flight ring.

The scheduler's request lifecycle (submit -> queued -> prefilling ->
decoding -> done/cancelled) and the engine's step phases (prefill lane,
decode chunk, harvest) are recorded as SPANS into a bounded ring. Two
export shapes read the same ring:

- ``chrome_trace()`` / ``write_chrome_trace(path)``: the Chrome
  trace-event format (a ``{"traceEvents": [...]}`` object of "X"
  complete events, ts/dur in microseconds, sorted by ts) — loadable
  directly in Perfetto / chrome://tracing. Request lifecycle phases ride
  tid=rid so one request reads as one track; engine step phases ride
  tid=0.
- ``jsonl_lines()`` / ``write_jsonl(path)``: one JSON object per event,
  newest-last — the flight recorder a crash handler or a log shipper
  tails.

The ring is a ``collections.deque(maxlen=capacity)``: memory is bounded
whatever the run length, and the newest events win (a flight recorder
keeps the crash, not the boot). Span counts and summed span seconds per
name are tracked EXACTLY (counters, not ring occupancy) so a reader can
tell how many spans each phase emitted, and what they took, even after
the ring wrapped.

ONE CALL, TWO SINKS. ``timed(name, **args)`` and ``instant(name, **args)``
write the ring AND a ``jax.profiler.TraceAnnotation(name, **args)`` under
the SAME name, so a phase or a request transition that the Chrome export
shows is also on the profiler's clock, beside the device's operations, in
a ``jax.profiler`` capture (the keyword arguments arrive there as the
event's stats; a ``tid`` other than 0 rides along as one more). Outside a
capture the annotation costs one flag test. ``span(name, start, end)``
records after the fact and so reaches the ring only.

``NullRecorder`` is the telemetry-off stand-in: same surface, no work.

``process_recorder()`` is the PROCESS's one ``SpanRecorder``: what the
process did on its way to ready (``setup/*``, ``compile/*``: the names in
docs/OBSERVABILITY.md), never a request or a step. It is real whatever an
engine's ``telemetry`` switch says.
"""

import collections
import contextlib
import json
import time


def _profiler_annotation():
    """``jax.profiler.TraceAnnotation``, or a stand-in that does nothing
    where jax is absent (this package imports clean without it)."""
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:
        return lambda name, **args: contextlib.nullcontext()
    return TraceAnnotation


class SpanRecorder(object):
    def __init__(self, capacity=4096, clock=time.time, pid=0):
        if capacity < 1:
            raise ValueError("trace ring capacity must be >= 1")
        self.capacity = capacity
        self._clock = clock
        self._pid = pid
        self._annotation = _profiler_annotation()
        self._ring = collections.deque(maxlen=capacity)
        self._counts = {}
        self._seconds = {}
        # ts=0 on both clocks, read back to back: a reader of a time on
        # ``time.perf_counter`` converts once, exactly.
        self._t0 = clock()
        self._t0_perf = time.perf_counter()
        self.dropped = 0

    # ------------------------------------------------------------ record

    def _emit(self, ev):
        if len(self._ring) == self._ring.maxlen:
            self.dropped += 1
        self._ring.append(ev)
        name = ev["name"]
        self._counts[name] = self._counts.get(name, 0) + 1

    def span(self, name, start, end=None, tid=0, **args):
        """One complete ("X") span: ``start``/``end`` are wall-clock
        seconds (``end`` defaults to now). Args must be JSON-safe."""
        if end is None:
            end = self._clock()
        self._seconds[name] = self._seconds.get(name, 0.0) \
            + max(end - start, 0.0)
        self._emit({
            "name": name,
            "ph": "X",
            "ts": (start - self._t0) * 1e6,
            "dur": max(end - start, 0.0) * 1e6,
            "pid": self._pid,
            "tid": tid,
            "args": args,
        })

    def _profiled(self, name, tid, args):
        if tid:
            return self._annotation(name, tid=tid, **args)
        return self._annotation(name, **args)

    def instant(self, name, tid=0, **args):
        """One instant ("i") event in the ring, and a zero-length
        annotation of the same name on the profiler's clock."""
        with self._profiled(name, tid, args):
            pass
        self._emit({
            "name": name,
            "ph": "i",
            "s": "t",
            "ts": (self._clock() - self._t0) * 1e6,
            "pid": self._pid,
            "tid": tid,
            "args": args,
        })

    class _Timed(object):
        __slots__ = ("rec", "name", "tid", "args", "_start", "_ann")

        def __init__(self, rec, name, tid, args):
            self.rec = rec
            self.name = name
            self.tid = tid
            self.args = args
            self._start = None
            self._ann = None

        def __enter__(self):
            self._ann = self.rec._profiled(self.name, self.tid, self.args)
            self._ann.__enter__()
            self._start = self.rec._clock()
            return self

        def __exit__(self, *exc):
            self.rec.span(self.name, self._start, tid=self.tid, **self.args)
            self._ann.__exit__(*exc)
            return False

    def timed(self, name, tid=0, **args):
        """Context manager: one span around the body, in the ring and
        (same name, same arguments) on the profiler's clock."""
        return self._Timed(self, name, tid, args)

    # ------------------------------------------------------------ export

    @property
    def epoch(self):
        """Wall-clock second this recorder's ts=0 maps to. The fleet
        merge (telemetry/distributed.py) re-anchors every ring to one
        shared epoch with this."""
        return self._t0

    @property
    def epoch_perf(self):
        """``time.perf_counter()`` at the moment ``epoch`` was read."""
        return self._t0_perf

    def span_counts(self):
        """Exact per-name event counts since construction (survives ring
        wraparound)."""
        return dict(self._counts)

    def span_seconds(self):
        """Exact per-name summed span seconds since construction (survives
        ring wraparound). Spans of one name that nest or overlap are summed
        twice: a reader that wants their union needs the events."""
        return dict(self._seconds)

    def events(self):
        return list(self._ring)

    def chrome_trace(self):
        """Perfetto-loadable trace object: events sorted by ts (the
        ring appends in wall order already, but spans are recorded at
        their END — a long span that finishes after a short one started
        later would otherwise appear out of order)."""
        events = sorted(self._ring, key=lambda e: e["ts"])
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def write_chrome_trace(self, path):
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
            f.write("\n")
        return path

    def jsonl_lines(self):
        return [json.dumps(e) for e in self._ring]

    def write_jsonl(self, path):
        with open(path, "w") as f:
            for line in self.jsonl_lines():
                f.write(line)
                f.write("\n")
        return path


class NullRecorder(object):
    """Telemetry-off stand-in: same surface, no allocation, no work."""

    capacity = 0
    dropped = 0
    epoch = 0.0
    epoch_perf = 0.0

    class _Null(object):
        __slots__ = ()

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    _null = _Null()

    def span(self, name, start, end=None, tid=0, **args):
        pass

    def instant(self, name, tid=0, **args):
        pass

    def timed(self, name, tid=0, **args):
        return self._null

    def span_counts(self):
        return {}

    def span_seconds(self):
        return {}

    def events(self):
        return []

    def chrome_trace(self):
        return {"traceEvents": [], "displayTimeUnit": "ms"}

    def write_chrome_trace(self, path):
        raise RuntimeError("telemetry is disabled: no trace to write")

    def jsonl_lines(self):
        return []

    def write_jsonl(self, path):
        raise RuntimeError("telemetry is disabled: no trace to write")


# Room for every event of a start-up with room to spare: the readers cut by
# time, so they need the events and not only the totals. What fills it is
# not the programs (1,042 at 128 slots, three spans each) but the ``jit``s
# traced INSIDE a step's trace, some 370 a layer: 9,925 events at GPT-2
# 355M's 24 layers, 11,613 in the Jamba cell, 17,995 at GPT-2 XL's 48 with
# remat (chip runs, PR 53: 16,384 wrapped there). 65,536 events are some
# 30 MB at the most.
PROCESS_RING = 65536
_PROCESS = []


def process_recorder():
    """The process's one ``SpanRecorder``, created on first use."""
    if not _PROCESS:
        _PROCESS.append(SpanRecorder(capacity=PROCESS_RING))
    return _PROCESS[0]
