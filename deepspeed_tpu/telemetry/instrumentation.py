"""JAX/XLA instrumentation: recompile detection + the compile listeners.

Two pieces, both safe when jax is absent or old:

- ``RecompileDetector``: turns the test-only ``compile_count == 1``
  contract into a RUNTIME gauge. Watches a set of jitted callables
  (anything exposing ``_cache_size()``), exposes the live total as a
  registry gauge, and after ``mark_warm()`` counts every further cache
  miss as a RECOMPILE (counter + one warning log per event, naming the
  program that grew and the seconds its trace, lowering and compile
  took). A mixed serving workload is expected to hold recompiles at 0
  forever — when it doesn't, the warning is the page.

- ``install_compile_listeners()``: the process's ONE set of
  ``jax.monitoring`` listeners, installed once (the last line of
  ``deepspeed_tpu/__init__.py``). Every program JAX traces, lowers and
  compiles (or reads from the persistent cache) becomes three spans in
  ``process_recorder()``, by the name JAX gives the program:
  ``compile/trace``, ``compile/lower`` and ``compile/backend`` (with
  ``cache_hit`` and ``retrieval_s`` where the cache was asked), on the
  ring's own clock (JAX stamps with ``time.time``). The same listeners
  feed the counters ``count_compiles_into(registry)`` registers:
  ``programs_compiled``, ``programs_cache_missed`` and the summed
  ``compile_trace_seconds`` / ``compile_lower_seconds`` /
  ``compile_backend_seconds``. An inner ``jit`` traced inside an outer one
  emits its own span inside the outer's: ``startup_summary()`` (what
  ``engine.metrics()["startup"]`` holds) totals the UNION of intervals and
  tables SELF time by program, never a plain sum.
"""

import re
import sys
import threading
import time
import weakref

from deepspeed_tpu.telemetry.tracing import process_recorder
from deepspeed_tpu.utils.logging import logger

# JAX's event -> (the span's name, the counter of its summed seconds).
_COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration":
        ("compile/trace", "compile_trace_seconds"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        ("compile/lower", "compile_lower_seconds"),
    "/jax/core/compile/backend_compile_duration":
        ("compile/backend", "compile_backend_seconds"),
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
# ``startup_seconds{phase=}``: ``startup_summary()``'s ``<phase>_s``.
_PHASES = ("import", "engine_init", "trace", "lower", "compile",
           "first_step", "ready")
_COUNTERS = ("programs_compiled", "programs_cache_missed") + tuple(
    counter for _, counter in _COMPILE_SPANS.values())

_installed = []
# What the persistent cache said of the compile under way on this thread:
# its events carry no name and fire INSIDE the backend span that ends next.
_pending = threading.local()
# registry -> its five counters; an engine's registry goes with the engine.
_sinks = weakref.WeakKeyDictionary()


def _on_time_span(event, start, end, **kw):
    known = _COMPILE_SPANS.get(event)
    if known is None:
        return
    name, counter = known
    args = {"fun_name": str(kw.get("fun_name", ""))}
    backend = name == "compile/backend"
    if backend:
        args.update(vars(_pending))
        vars(_pending).clear()
    process_recorder().span(name, start, end, **args)
    for counters in list(_sinks.values()):
        counters[counter].inc(max(end - start, 0.0))
        if backend:
            counters["programs_compiled"].inc()


def _on_duration(event, seconds, **_):
    if event == _CACHE_RETRIEVAL:
        _pending.retrieval_s = seconds


def _on_event(event, **_):
    if event == _CACHE_HIT:
        _pending.cache_hit = True
    elif event == _CACHE_MISS:
        _pending.cache_hit = False
        for counters in list(_sinks.values()):
            counters["programs_cache_missed"].inc()


def install_compile_listeners():
    """Register the three listeners above with ``jax.monitoring``, once a
    process however often it is called. False where jax cannot be
    imported (``deepspeed_tpu.telemetry`` alone imports without it)."""
    if _installed:
        return True
    try:
        from jax import monitoring
    except ImportError:
        return False
    _installed.append(True)
    monitoring.register_event_time_span_listener(_on_time_span)
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)
    return True


def count_compiles_into(registry):
    """Have the listeners feed ``registry``'s counters of compiles (from
    now on: what compiled before the registry existed is in the process
    recorder), and give it the ``startup_seconds{phase=}`` gauges."""
    _sinks[registry] = {name: registry.counter(name) for name in _COUNTERS}
    for phase in _PHASES:
        registry.gauge("startup_seconds", phase=phase).set_fn(
            lambda key=phase + "_s": startup_summary()[key] or 0.0)


def mark_ready(engine):
    """The instant ``setup/ready`` of ``engine`` (``inference`` |
    ``training``): its step has compiled and run once. ``since_import_s``:
    the seconds since ``setup/import`` began, the earliest moment the
    program knows."""
    began = getattr(sys.modules.get("deepspeed_tpu"), "_import_started",
                    None)
    process_recorder().instant(
        "setup/ready", engine=engine,
        since_import_s=None if began is None else time.time() - began)


_WRAPPED = re.compile(r"^\w+\((.*)\)$")


def program_of(fun_name):
    """The program a ``fun_name`` belongs to: JAX names a trace by the
    function (``mixed_step``) and its lowering and compile by the module
    (``jit(mixed_step)``)."""
    m = _WRAPPED.match(fun_name or "")
    return m.group(1) if m else (fun_name or "")


def _union_length(intervals):
    total, reach = 0.0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            total += end - start
            reach = end
        elif end > reach:
            total += end - reach
            reach = end
    return total


def _self_lengths(spans):
    """``[(start, end)]`` -> the length of each span that no span nested
    DIRECTLY inside it covers, in the order given."""
    own = [end - start for start, end in spans]
    stack = []
    for i in sorted(range(len(spans)),
                    key=lambda i: (spans[i][0], -spans[i][1])):
        start, end = spans[i]
        while stack and spans[stack[-1]][1] <= start:
            stack.pop()
        if stack and end <= spans[stack[-1]][1]:
            own[stack[-1]] -= end - start
        stack.append(i)
    return [max(s, 0.0) for s in own]


def compile_seconds(program):
    """{"trace", "lower", "backend"} -> seconds of the NEWEST span of each
    kind whose program is ``program`` (parts never seen are left out)."""
    out = {}
    for ev in reversed(process_recorder().events()):
        part = ev["name"].partition("compile/")[2]
        if part and part not in out and ev["ph"] == "X" and \
                program_of(ev["args"].get("fun_name")) == program:
            out[part] = ev["dur"] / 1e6
            if len(out) == 3:
                break
    return out


_summary = [None, None]


def startup_summary(top=5):
    """The process's way to ready, from ``process_recorder()``:
    ``import_s``, ``engine_init_s`` and ``first_step_s`` (the newest span
    of each), ``ready_s`` (``setup/import``'s start to the newest
    ``setup/ready``; None until the newest engine is ready), ``trace_s`` /
    ``lower_s`` / ``compile_s`` (the UNION of the ``compile/*`` spans that ended before
    ready, or of all where the process is not ready yet), ``programs``
    (``compile/backend`` spans among them), ``cache_misses`` (those
    compiled for real) and ``slowest``: ``[fun_name, trace_s, lower_s,
    backend_s, cache_hit]`` of the ``top`` programs by SELF seconds. In a
    process that runs several engines in turn the compiles count from the
    last ``engine/closed`` before the newest ``setup/engine_init``.
    Computed anew only when the recorder has seen an event since."""
    rec = process_recorder()
    seen = (sum(rec.span_counts().values()), top)
    if _summary[0] == seen:
        return _summary[1]
    events = rec.events()
    newest = {}
    for ev in events:
        if ev["name"].startswith(("setup/", "engine/")):
            newest[ev["name"]] = ev

    def seconds(name):
        ev = newest.get(name)
        return ev["dur"] / 1e6 if ev is not None else None

    init, ready = newest.get("setup/engine_init"), newest.get("setup/ready")
    if ready is not None and init is not None and ready["ts"] < init["ts"]:
        ready = None  # the newest engine is still on its way
    since = float("-inf") if init is None else max(
        [ev["ts"] for ev in events if ev["name"] == "engine/closed"
         and ev["ts"] <= init["ts"]], default=float("-inf"))
    until = ready["ts"] if ready is not None else float("inf")
    spans = [ev for ev in events if ev["name"].startswith("compile/")
             and ev["ts"] >= since and ev["ts"] + ev["dur"] <= until]
    own = _self_lengths([(ev["ts"], ev["ts"] + ev["dur"]) for ev in spans])
    programs = {}
    for ev, self_us in zip(spans, own):
        row = programs.setdefault(program_of(ev["args"].get("fun_name")),
                                  {"trace": 0.0, "lower": 0.0,
                                   "backend": 0.0, "cache_hit": None})
        row[ev["name"].partition("/")[2]] += self_us / 1e6
        if "cache_hit" in ev["args"]:
            row["cache_hit"] = ev["args"]["cache_hit"]

    def union(name):
        return _union_length([(ev["ts"], ev["ts"] + ev["dur"])
                               for ev in spans if ev["name"] == name]) / 1e6

    backends = [ev for ev in spans if ev["name"] == "compile/backend"]
    slowest = sorted(programs.items(), key=lambda kv: -(
        kv[1]["trace"] + kv[1]["lower"] + kv[1]["backend"]))[:top]
    out = {
        # exact whatever the ring has dropped since (one import a process)
        "import_s": rec.span_seconds().get("setup/import"),
        "engine_init_s": seconds("setup/engine_init"),
        "trace_s": union("compile/trace"),
        "lower_s": union("compile/lower"),
        "compile_s": union("compile/backend"),
        "first_step_s": seconds("setup/first_step"),
        "ready_s": None if ready is None
        else ready["args"]["since_import_s"],
        "programs": len(backends),
        "cache_misses": sum(1 for ev in backends
                            if ev["args"].get("cache_hit") is False),
        "slowest": [[name, row["trace"], row["lower"], row["backend"],
                     row["cache_hit"]] for name, row in slowest],
    }
    _summary[:] = [seen, out]
    return out


class RecompileDetector(object):
    """Live compile-count gauge + post-warmup recompile counter over a
    set of jitted programs.

    ``registry`` is a MetricsRegistry (or NullRegistry); ``watch(label,
    jitted)`` registers a program (label lands in the warning and the
    per-program gauge); ``observe()`` re-reads every cache and updates
    the gauges — call it at step boundaries (cheap: one int read per
    program). ``mark_warm()`` freezes the expected total; any growth
    past it increments the ``recompiles`` counter and logs a warning
    naming the offender and the seconds its newest trace, lowering and
    compile took (found in the process recorder by ``label``: name a
    program as its function is named). ``describe`` is an optional ``label -> str``
    hook (the xray ProgramRegistry's ``identity``) that lets the
    warning name the exact program: HLO fingerprint plus old -> new
    shape signature — the same identity key the autopsy reports, so
    the page and the post-mortem agree on WHICH program recompiled."""

    def __init__(self, registry, describe=None, **labels):
        self._registry = registry
        self._labels = labels
        self._describe = describe
        self._programs = {}
        self._last = {}
        self._warm_total = None
        self.gauge = registry.gauge("compile_count", **labels)
        self.recompiles = registry.counter("recompiles", **labels)
        self.gauge.set_fn(self.total)

    def watch(self, label, jitted):
        if not hasattr(jitted, "_cache_size"):
            raise TypeError(
                "RecompileDetector.watch({!r}): object has no _cache_size()"
                " — pass the jax.jit wrapper itself".format(label))
        self._programs[label] = jitted
        self._last[label] = 0
        return jitted

    def total(self):
        return sum(p._cache_size() for p in self._programs.values())

    @property
    def warm(self):
        return self._warm_total is not None

    def mark_warm(self):
        """Freeze the expected compile total at its current value: every
        later growth is a recompile. Re-observing first so compiles that
        already happened are not misread as post-warmup."""
        self.observe()
        self._warm_total = self.total()
        return self._warm_total

    def observe(self):
        """Re-read every watched cache; returns the number of NEW
        post-warmup compiles seen by this call (0 during warmup)."""
        new_after_warm = 0
        for label, prog in self._programs.items():
            size = prog._cache_size()
            grew = size - self._last[label]
            if grew > 0:
                self._last[label] = size
                if self._warm_total is not None:
                    new_after_warm += grew
                    self.recompiles.inc(grew)
                    ident = ""
                    if self._describe is not None:
                        try:
                            got = self._describe(label)
                            if got:
                                ident = " [{}]".format(got)
                        except Exception:
                            ident = ""
                    # What the operator lost: the newest trace, lowering
                    # and compile (or cache read) of that program.
                    cost = compile_seconds(label)
                    logger.warning(
                        "telemetry: program %r recompiled (%d new "
                        "compilation%s, total compile_count=%d) after "
                        "warmup — a traced value became static or a "
                        "shape changed; it cost %.3f s (trace %.3f, lower "
                        "%.3f, backend %.3f)%s", label, grew,
                        "" if grew == 1 else "s", self.total(),
                        sum(cost.values()), cost.get("trace", 0.0),
                        cost.get("lower", 0.0), cost.get("backend", 0.0),
                        ident)
        return new_after_warm
