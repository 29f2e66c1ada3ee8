"""JAX/XLA instrumentation: recompile detection + profiler hooks.

Two pieces, both optional and both safe when jax is absent or old:

- ``RecompileDetector``: turns the test-only ``compile_count == 1``
  contract into a RUNTIME gauge. Watches a set of jitted callables
  (anything exposing ``_cache_size()``), exposes the live total as a
  registry gauge, and after ``mark_warm()`` counts every further cache
  miss as a RECOMPILE (counter + one warning log per event, naming the
  program that grew). A mixed serving workload is expected to hold
  recompiles at 0 forever — when it doesn't, the warning is the page.

- ``profile_window()``: a ``DS_TPU_PROFILE_DIR``-gated
  ``jax.profiler.trace`` capture. When the env var is unset (the
  default), it is a no-op context; when set, the body runs under a
  profiler trace written beneath that directory. One capture at a time
  per process (jax's own constraint) — nested/concurrent windows
  degrade to no-ops rather than raising mid-serve.
"""

import contextlib
import os

from deepspeed_tpu.utils.logging import logger

PROFILE_DIR_ENV = "DS_TPU_PROFILE_DIR"


class RecompileDetector(object):
    """Live compile-count gauge + post-warmup recompile counter over a
    set of jitted programs.

    ``registry`` is a MetricsRegistry (or NullRegistry); ``watch(label,
    jitted)`` registers a program (label lands in the warning and the
    per-program gauge); ``observe()`` re-reads every cache and updates
    the gauges — call it at step boundaries (cheap: one int read per
    program). ``mark_warm()`` freezes the expected total; any growth
    past it increments the ``recompiles`` counter and logs a warning
    naming the offender. ``describe`` is an optional ``label -> str``
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
                    logger.warning(
                        "telemetry: program %r recompiled (%d new "
                        "compilation%s, total compile_count=%d) after "
                        "warmup — a traced value became static or a "
                        "shape changed%s", label, grew,
                        "" if grew == 1 else "s", self.total(), ident)
        return new_after_warm


_profile_active = [False]


@contextlib.contextmanager
def profile_window(subdir=None):
    """Profiler capture window gated on ``DS_TPU_PROFILE_DIR``.

    Unset env (the default): pure no-op. Set: the body runs under
    ``jax.profiler.trace(dir)`` and the capture lands beneath the
    directory (plus ``subdir`` when given). A second window while one
    is active no-ops instead of raising — profiling must never take
    the serving loop down."""
    base = os.environ.get(PROFILE_DIR_ENV)
    if not base or _profile_active[0]:
        yield None
        return
    path = os.path.join(base, subdir) if subdir else base
    # Setup failures (no jax, unwritable dir, profiler already active
    # out-of-band) degrade to a no-op window; a failure INSIDE the body
    # must propagate untouched, so enter/exit are guarded separately.
    try:
        import jax

        os.makedirs(path, exist_ok=True)
        cm = jax.profiler.trace(path)
        cm.__enter__()
    except Exception as e:
        logger.warning("telemetry: profiler capture under %s failed (%s); "
                       "continuing without it", path, e)
        yield None
        return
    _profile_active[0] = True
    try:
        yield path
    finally:
        _profile_active[0] = False
        try:
            cm.__exit__(None, None, None)
        except Exception as e:
            logger.warning("telemetry: profiler capture finalize under %s "
                           "failed (%s)", path, e)
