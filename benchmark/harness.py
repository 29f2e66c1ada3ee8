"""Everything ``run.py`` does after it has checked the device: find the cell's
files by name, run its driver, reduce the trace, read the per-layer metrics
and build the result line. Driven by data throughout: a cell, a
configuration, a traffic mix, a driver and a per-layer metric are each a file
found by its name, so a later PR adds one by adding files and entries.
"""

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
# What a run leaves behind (traces), inside the checkout and in .gitignore.
OUT_DIR = os.path.join(ROOT, ".bench_out")

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_EVENTS = ("/jax/compilation_cache/cache_hits",
                 "/jax/compilation_cache/cache_misses")


_T0 = time.perf_counter()


def note(**fields):
    """One of the run's earlier lines: a JSON object on standard output that
    tells a reader of the log why a number moved, stamped with the seconds
    since the harness was imported. Never the last line."""
    print(json.dumps(dict(fields, t=round(time.perf_counter() - _T0, 3)),
                     default=str), flush=True)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def paths():
    """``paths`` of ``BENCHMARK.json``: the directories every file of the
    benchmark is looked up under. A manifest the tests build keeps them."""
    return load_json(MANIFEST)["paths"]


def _find(paths, *parts):
    """The first ``<path>/<parts...>`` that exists under the manifest's
    ``paths``: how every per-cell file is found by its name."""
    tried = []
    for base in paths:
        candidate = os.path.join(ROOT, base, *parts)
        tried.append(candidate)
        if os.path.exists(candidate):
            return candidate
    raise FileNotFoundError("none of {} exists".format(tried))


def find_all(folder, suffix):
    """Every ``<path>/<folder>/*<suffix>`` under ``paths``, sorted within a
    path: the files a later PR adds beside the ones that are there (names
    files, stand-ins, builders)."""
    out = []
    for base in paths():
        found = os.path.join(ROOT, base, folder)
        if os.path.isdir(found):
            out += [os.path.join(found, f) for f in sorted(os.listdir(found))
                    if f.endswith(suffix)]
    return out


def load_by_name(folder, name):
    """The module ``<path>/<folder>/<name>.py`` under ``paths``."""
    return _load_module(_find(paths(), folder, name + ".py"),
                        "benchmark_{}_{}".format(folder, name))


def _load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Cell(object):
    """One entry of ``workloads`` with its configuration and traffic files
    read: everything a driver needs to know about what to run."""

    def __init__(self, manifest, name):
        self.manifest = manifest
        rows = [w for w in manifest["workloads"] if w["name"] == name]
        if len(rows) != 1:
            raise KeyError("workload {!r} is not in the manifest (has: {})"
                           .format(name, [w["name"]
                                          for w in manifest["workloads"]]))
        row = rows[0]
        self.name = name
        self.chips = int(row["chips"])
        self.config_name = row["config"]
        self.traffic_name = row["traffic"]
        cfg = [c for c in manifest["configs"] if c["name"] == row["config"]]
        if len(cfg) != 1:
            raise KeyError("configuration {!r} is not in the manifest"
                           .format(row["config"]))
        self.config = load_json(os.path.join(ROOT, cfg[0]["file"]))
        self.traffic = load_json(_find(
            manifest["paths"], "workloads", row["traffic"] + ".json"))

    def metrics(self, section):
        """The manifest's metrics of ``section`` that this cell reports."""
        return [m for m in self.manifest[section]
                if "workloads" not in m or self.name in m["workloads"]]


def load_model(cell):
    """The cell's model, built by the builder its ``model_type`` names."""
    kind = cell.config["model_type"]
    return _load_module(
        _find(cell.manifest["paths"], "model_builders", kind + ".py"),
        "benchmark_model_" + kind).Model(cell.config)


class CompileLog(object):
    """JAX's own record of compilations and of the persistent cache, by
    listening to ``jax.monitoring``."""

    def __init__(self):
        import jax.monitoring

        self.compiles = 0
        self.cache = {"cache_hits": 0, "cache_misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, _seconds, **_):
        if event == _COMPILE_EVENT:
            self.compiles += 1

    def _event(self, event, **_):
        if event in _CACHE_EVENTS:
            self.cache[event.rsplit("/", 1)[-1]] += 1


class Run(object):
    """What a driver is handed: the cell, the arguments, the devices, the
    clock that ``setup_s`` is read from, and the traced window."""

    def __init__(self, cell, seed, seconds, trace, devices, started):
        self.cell = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.devices = list(devices)[:cell.chips]
        self.started = started
        self.setup_s = None
        self.compile_log = CompileLog()
        self.model = load_model(cell)
        self._compiles_at_open = None
        self.compiles_in_window = None
        self.trace_dir = None

    def window_opens(self):
        """Set-up ends here: everything before is loading, compiling and
        warming up."""
        self.setup_s = time.perf_counter() - self.started
        self._compiles_at_open = self.compile_log.compiles
        note(event="window_opens", setup_s=self.setup_s,
             compiles_in_setup=self.compile_log.compiles,
             **self.compile_log.cache)

    def window_closes(self):
        """The measured window ends here; what follows (the traced tail,
        the comparison with the reference) may compile."""
        self.compiles_in_window = \
            self.compile_log.compiles - self._compiles_at_open

    @contextlib.contextmanager
    def traced(self):
        """Profile what runs inside: the short sub-window of a ``--trace 1``
        run. The Python tracer stays off (it floods the host and the file)."""
        import jax

        self.trace_dir = os.path.join(OUT_DIR, "trace", self.cell.name)
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(self.trace_dir)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation("bench/window"):
                yield
        finally:
            jax.profiler.stop_trace()


def median_chunk_rate(done_t, units, chunk):
    """Work per second as the MEDIAN over chunks of ``chunk`` consecutive
    steps of (units the chunk finished) / (time from the boundary before it
    to its last boundary). ``done_t[i]`` is the time step i finished and
    ``units[i]`` what it finished; step 0 only marks the first boundary. A
    stall of the machine (2 of this PR's 44 chip runs lost 2 s and 7 s to
    one) lands in one chunk and leaves the median alone; a slowdown that
    recurs within a chunk shows in full. With fewer steps than one chunk,
    the whole span is the one chunk."""
    import numpy as np

    n = len(done_t) - 1
    chunk = min(chunk, n)
    rates = [sum(units[i + 1:i + 1 + chunk]) / (done_t[i + chunk] - done_t[i])
             for i in range(0, n - chunk + 1, chunk)]
    return float(np.median(rates)), len(rates)


def span(name):
    """A host span on the profiler's clock, from the benchmark's own files."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def peak_bytes(devices):
    """Peak memory on the fullest chip; None where the backend keeps no
    statistics (the CPU). The TPU's allocator counts the buffers a program
    is handed and returns (``peak_bytes_in_use``) apart from the scratch a
    running program reserves (``peak_bytes_reserved``, the compiler's temp):
    the chip holds both, so the peak is their sum."""
    stats = [s for s in (d.memory_stats() for d in devices) if s]
    note(event="memory", stats=stats)
    peaks = [int(s["peak_bytes_in_use"]) + int(s.get("peak_bytes_reserved", 0))
             for s in stats if "peak_bytes_in_use" in s]
    return max(peaks) if peaks else None


def run_cell(manifest, workload, seed, seconds, trace, devices,
             started=None, trace_names=None):
    """Run one cell once and return the result object of the contract.
    ``run.py`` prints it as the last line. ``manifest`` is the parsed
    ``BENCHMARK.json`` (the tests pass one of their own, and the names a
    CPU's trace has in place of ``kernel_names.json``)."""
    from benchmark import trace_reduce

    started = time.perf_counter() if started is None else started
    cell = Cell(manifest, workload)
    run = Run(cell, seed, seconds, trace, devices, started)
    note(event="cell", workload=cell.name, config=cell.config_name,
         traffic=cell.traffic_name, chips=cell.chips, seed=run.seed,
         seconds=run.seconds, trace=run.trace,
         config_file=cell.config, traffic_file=cell.traffic)
    kind = cell.traffic["kind"]
    driver = _load_module(_find(manifest["paths"], "drivers", kind + ".py"),
                          "benchmark_driver_" + kind)
    outcome = driver.run(run)
    if run.setup_s is None or run.compiles_in_window is None:
        raise RuntimeError("driver {!r} never opened or never closed its "
                           "window".format(kind))

    # A run that compiled inside its window measured the compiler.
    compiles = run.compiles_in_window
    correct = bool(outcome["correct"]) and compiles == 0
    note(event="window_closed", compiles_in_window=compiles,
         checks=outcome["checks"], **run.compile_log.cache)

    device = {"platform": run.devices[0].platform,
              "kind": run.devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak_bytes(run.devices)}
    values = dict(outcome["values"], setup_s=run.setup_s)
    result = {"correct": correct, "attempted": int(outcome["attempted"]),
              "failed": int(outcome["failed"])}
    if not run.trace:
        section = "end_to_end"
        readings = values
    else:
        section = "per_layer"
        reduced = trace_reduce.reduce_trace(
            trace_reduce.load(trace_reduce.find_xplane(run.trace_dir)),
            names=trace_names)
        if reduced is None:
            raise RuntimeError("the traced window holds no device operation")
        note(event="trace", window_s=reduced["window_s"],
             window_from=reduced["window_from"], devices=reduced["devices"],
             classes=reduced["classes"], calls=reduced["class_calls"],
             traced_steps=outcome["counters"].get("trace_steps"),
             spans=reduced["spans"])
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
        context = {"trace": reduced, "values": values,
                   "counters": outcome["counters"], "cell": cell,
                   "device": device}
        readings = {}
        for metric in cell.metrics(section):
            reader = _load_module(
                _find(manifest["paths"], "layer_metrics",
                      metric["name"].rsplit(".", 1)[-1] + ".py"),
                "benchmark_metric_" + metric["name"].replace(".", "_"))
            readings[metric["name"]] = reader.read(context)
    metrics = {}
    for metric in cell.metrics(section):
        value = readings.get(metric["name"])
        if value is not None:
            metrics[metric["name"]] = {"value": float(value),
                                       "unit": metric["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    return result


def main(argv, started):
    import argparse

    parser = argparse.ArgumentParser(
        description="Run one cell of BENCHMARK.json once.")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    manifest = load_json(MANIFEST)
    chips = Cell(manifest, args.workload).chips

    import deepspeed_tpu

    if os.path.dirname(os.path.dirname(os.path.abspath(
            deepspeed_tpu.__file__))) != ROOT:
        print("benchmark: deepspeed_tpu was imported from {}, not from this "
              "checkout".format(deepspeed_tpu.__file__), file=sys.stderr)
        return 3

    # Caches the program writes outside its compile cache (the autotune
    # table) go where the driver pointed XDG_CACHE_HOME, else inside the
    # checkout; never to a path two checkouts would share.
    os.environ.setdefault("XDG_CACHE_HOME",
                          os.path.join(ROOT, ".jax_cache", "xdg"))
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print("benchmark: {} needs {} TPU chip(s), JAX reports {} x {}"
              .format(args.workload, chips, len(devices),
                      devices[0].platform), file=sys.stderr)
        return 3

    from deepspeed_tpu.utils.compile_cache import place_compile_cache

    cache_dir = place_compile_cache()
    # Small programs are worth caching too: every run is a new process.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    note(event="start", compile_cache_dir=cache_dir,
         jax=jax.__version__, devices=[str(d) for d in devices])

    result = run_cell(manifest, args.workload, args.seed, args.seconds,
                      args.trace, devices, started)
    print(json.dumps(result), flush=True)
    return 0
