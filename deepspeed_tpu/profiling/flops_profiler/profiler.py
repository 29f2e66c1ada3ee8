"""Flops profiler — per-module flops/params/duration for a model
(reference deepspeed/profiling/flops_profiler/profiler.py:11-297).

The reference monkey-patches torch.nn.functional and installs forward hooks
to count MACs eagerly. Under XLA there is nothing to patch — the compiler
already knows the cost of the compiled program. So the TPU-native profiler
has two sources of truth:

- **exact program cost**: ``observe(jitted_fn, *args)`` is a thin client of
  the perf-xray ProgramRegistry (telemetry/xray.py — the one place that
  does AOT lower+compile and reads ``Compiled.cost_analysis()``), so the
  profiler's totals, the engine's roofline gauges, and the ``perf_xray()``
  section a report carries all come from the same records. The cost covers the real
  training program the engine ran — backward pass and fusion effects
  included, which the reference's functional-level MAC counting cannot see;
- **per-module breakdown**: flax's interpreter-mode tabulation
  (``nn.Module.tabulate(compute_flops=True)``) walks the module tree and
  costs each submodule, replacing the hook machinery.

API names follow the reference (start/stop/end_profile, get_total_flops/
duration/params, print_model_profile, print_model_aggregated_profile) plus
the convenience ``get_model_profile`` entry point.
"""

import time

import jax
import numpy as np

from deepspeed_tpu.utils.logging import logger


def number_to_string(num, units=None, precision=2):
    if units is None:
        if num >= 1e12:
            return "{:.{}f} T".format(num / 1e12, precision)
        if num >= 1e9:
            return "{:.{}f} G".format(num / 1e9, precision)
        if num >= 1e6:
            return "{:.{}f} M".format(num / 1e6, precision)
        if num >= 1e3:
            return "{:.{}f} K".format(num / 1e3, precision)
        return "{:.{}f}".format(num, precision)
    return "{:.{}f} {}".format(num, precision, units)


flops_to_string = number_to_string
params_to_string = number_to_string
macs_to_string = number_to_string


def duration_to_string(duration, precision=2):
    if duration > 1:
        return "{:.{}f} s".format(duration, precision)
    if duration * 1e3 > 1:
        return "{:.{}f} ms".format(duration * 1e3, precision)
    return "{:.{}f} us".format(duration * 1e6, precision)


class FlopsProfiler(object):
    """Profiles a flax model / jitted programs (reference profiler.py:11).

    ``xray`` is an optional shared telemetry.ProgramRegistry — the
    training engine passes its own so profiled programs land in the
    same observatory its perf_xray() exports; standalone use gets a
    private, unpublished registry. Either way the per-(program, shape)
    analysis is cached there: a profiled window pays one AOT compile
    per program, not one per step."""

    def __init__(self, model=None, xray=None):
        self.model = model
        self.started = False
        self._xray = xray
        self._labels = {}        # id(fn) -> (fn, label); fn ref pins id
        self._used_labels = set()
        self.reset_profile()

    # ----------------------------------------------------------- lifecycle
    def reset_profile(self):
        self._total_flops = 0.0
        self._total_bytes = 0.0
        self._observed = 0
        self._start_time = None
        self._duration = 0.0
        self._example_args = None
        self._example_kwargs = None

    def start_profile(self, ignore_list=None):
        self.reset_profile()
        self.started = True
        self._start_time = time.time()

    def stop_profile(self):
        if self._start_time is not None:
            self._duration = time.time() - self._start_time
        self.started = False

    def end_profile(self):
        self.reset_profile()

    # ------------------------------------------------------------ observers
    def _label_for(self, jitted_fn):
        """A registry label UNIQUE per program object: two distinct
        jitted fns sharing a ``__name__`` (two '<lambda>'s, two 'step's)
        must not collapse to one record — the registry dedupes on
        (label, signature), so a collision would silently double-count
        the first program's cost. The fn itself is held in the map:
        id() keys are only stable while the object is alive."""
        key = id(jitted_fn)
        entry = self._labels.get(key)
        if entry is not None:
            return entry[1]
        base = getattr(jitted_fn, "__name__", None) or "program"
        label, n = base, len(self._labels)
        while label in self._used_labels:
            label = "{}#{}".format(base, n)
            n += 1
        self._used_labels.add(label)
        self._labels[key] = (jitted_fn, label)
        return label

    def observe(self, jitted_fn, *args, **kwargs):
        """Record the XLA-compiled cost of one program invocation. The engine
        calls this with its fused fwd+bwd program, so totals reflect the real
        executed flops (fwd+bwd+update), not an estimate. Thin xray client:
        the ProgramRegistry owns the AOT compile, the fingerprint, and the
        per-(program, shapes) cache. ``tokens=`` is reserved for the
        registry's accounting, never forwarded to the program."""
        try:
            if self._xray is None:
                from deepspeed_tpu.telemetry import ProgramRegistry

                self._xray = ProgramRegistry()
            label = self._label_for(jitted_fn)
            record = self._xray.observe(label, jitted_fn, *args, **kwargs)
            self._total_flops += record["flops"]
            self._total_bytes += record["bytes_accessed"]
            self._observed += 1
        except Exception as e:  # cost analysis is best-effort
            logger.warning("flops observe failed: %s", e)

    def set_example_batch(self, *args, **kwargs):
        """Remember example inputs for the per-module tabulation."""
        self._example_args = args
        self._example_kwargs = kwargs

    # -------------------------------------------------------------- totals
    def get_total_flops(self, as_string=False):
        f = self._total_flops
        return flops_to_string(f) if as_string else f

    def get_total_duration(self, as_string=False):
        d = self._duration
        return duration_to_string(d) if as_string else d

    def get_total_params(self, as_string=False):
        n = 0
        if self._example_args is not None and hasattr(self.model, "init"):
            variables = jax.eval_shape(
                lambda: self.model.init(jax.random.PRNGKey(0),
                                        *self._example_args,
                                        **(self._example_kwargs or {})))
            n = sum(int(np.prod(x.shape)) for x in
                    jax.tree_util.tree_leaves(variables))
        return params_to_string(n) if as_string else n

    def get_total_steps(self):
        return self._observed

    # ------------------------------------------------------------- reports
    def _tabulate(self, depth=None):
        import flax.linen as nn
        if self.model is None or self._example_args is None or \
                not isinstance(self.model, nn.Module):
            return None
        try:
            return nn.tabulate(
                self.model, jax.random.PRNGKey(0), compute_flops=True,
                compute_vjp_flops=False,
                depth=depth)(*self._example_args,
                             **(self._example_kwargs or {}))
        except Exception as e:
            logger.warning("flops tabulate failed: %s", e)
            return None

    def print_model_profile(self, profile_step=1, module_depth=-1,
                            top_modules=3, detailed=True, output_file=None):
        lines = [
            "-------------------------- DeepSpeed Flops Profiler "
            "--------------------------",
            "Profile step: {}".format(profile_step),
            "Observed programs: {}".format(self._observed),
            "Total measured flops (XLA cost analysis): {}".format(
                self.get_total_flops(as_string=True)),
            "Total bytes accessed: {}".format(
                number_to_string(self._total_bytes, units="B")),
            "Profile duration: {}".format(
                self.get_total_duration(as_string=True)),
        ]
        table = self._tabulate(
            depth=None if module_depth in (-1, None) else module_depth)
        if table is not None:
            lines.append(table)
        out = "\n".join(str(x) for x in lines)
        if output_file:
            with open(output_file, "w") as f:
                f.write(out)
        else:
            print(out)
        return out

    def print_model_aggregated_profile(self, module_depth=-1, top_modules=3):
        table = self._tabulate(depth=1 if module_depth in (-1, None)
                               else module_depth)
        if table is not None:
            print(table)
        return table


def get_model_profile(model,
                      args=(),
                      kwargs=None,
                      print_profile=True,
                      detailed=True,
                      module_depth=-1,
                      top_modules=3,
                      warm_up=1,
                      as_string=True,
                      output_file=None,
                      ignore_modules=None):
    """One-shot profiling helper (reference profiler.py module entry): returns
    (flops, params) for a flax model applied to example args."""
    prof = FlopsProfiler(model)
    prof.start_profile()
    prof.set_example_batch(*args, **(kwargs or {}))

    variables = model.init(jax.random.PRNGKey(0), *args, **(kwargs or {}))
    fn = jax.jit(lambda v, *a: model.apply(v, *a, **(kwargs or {})))
    for _ in range(max(warm_up, 1)):
        jax.block_until_ready(fn(variables, *args))
    prof.observe(fn, variables, *args)
    prof.stop_profile()

    flops = prof.get_total_flops(as_string=as_string)
    params = prof.get_total_params(as_string=as_string)
    if print_profile:
        prof.print_model_profile(module_depth=module_depth,
                                 top_modules=top_modules,
                                 output_file=output_file)
    prof.end_profile()
    return flops, params
