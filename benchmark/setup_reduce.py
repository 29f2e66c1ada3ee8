"""From the program's own record of its start-up to the parts of ``setup_s``.

The program keeps, at process scope, what it did on its way to ready
(``deepspeed_tpu.telemetry.process_recorder()``, docs/OBSERVABILITY.md):
``setup/import``, ``setup/engine_init``, ``setup/first_step``, the instants
``setup/ready`` and ``engine/closed``, and for EVERY program JAX traced,
lowered and compiled (or read from the persistent cache) the three spans
``compile/trace``, ``compile/lower`` and ``compile/backend`` with the
program's name. A reader is called in the process the driver ran in, after
the driver, so the record is simply there. On a program without such a
recorder (every commit before PR 53) ``of_run`` returns nothing, every
reader returns nothing and the line leaves the metrics out.

The run starts at the process's start (``harness._T0``: ``run.py`` runs one
cell a process; where a test process runs many, at the last ``engine/closed``
before the newest ``setup/engine_init``, if that is later) and the window
opens ``setup_s`` after it, both on ``time.perf_counter``, the clock
``setup_s`` is read from; the recorder states its epoch on that clock
(``epoch_perf``). Only events that END before the window opens count: what
compiles after it (the reference's check, the traced tail) is not set-up.

The stretch from start to window is cut into parts that do not overlap:

- ``boot_s``: start to the first ``compile/*`` or ``setup/engine_init``
  (interpreter, imports, chip start-up, cache placement);
- the UNION of all ``compile/*`` spans (an inner ``jit`` traced inside an
  outer one lies inside the outer's span: never a sum), also by kind:
  ``trace_s``, ``lower_s``, ``compile_s``;
- ``engine_init_s``: ``setup/engine_init`` less the ``compile/*`` inside it;
- ``warm_s``: ``setup/ready`` to the window less the ``compile/*`` inside it
  (the driver's warm-up on a program that is ready);
- ``unnamed_s``: the rest (the weights' and the first step's execution, the
  reference's run), so that ``boot + engine_init + compile/* + unnamed + warm
  = setup_s`` by construction.
"""

import re

from benchmark import trace_reduce

KINDS = {"compile/trace": "trace", "compile/lower": "lower",
         "compile/backend": "backend"}
_WRAPPED = re.compile(r"^\w+\((.*)\)$")


def program_of(fun_name):
    """JAX names a trace by the function (``mixed_step``) and its lowering
    and compile by the module (``jit(mixed_step)``): one program."""
    m = _WRAPPED.match(fun_name or "")
    return m.group(1) if m else (fun_name or "")


def recorder():
    """The program's process recorder; None on a program without one."""
    try:
        from deepspeed_tpu.telemetry import process_recorder
    except ImportError:
        return None
    return process_recorder()


def on_run_clock(rec):
    """The recorder's events as ``(name, start, end, args)`` in seconds on
    ``time.perf_counter`` (an instant's end is its start)."""
    base = rec.epoch_perf
    return [(ev["name"], base + ev["ts"] / 1e6,
             base + (ev["ts"] + ev.get("dur", 0.0)) / 1e6, ev["args"])
            for ev in rec.events()]


def run_start(events, process_start):
    """Where this run's set-up began: the process's start, or the last
    ``engine/closed`` before the newest ``setup/engine_init`` if later."""
    inits = [s for name, s, _, _ in events if name == "setup/engine_init"]
    if not inits:
        return process_start
    closed = [s for name, s, _, _ in events
              if name == "engine/closed" and s <= max(inits)]
    return max([process_start] + closed)


def reduce_setup(events, start, opens, top=10):
    """The parts of ``[start, opens]`` (module docstring) from ``events`` as
    ``on_run_clock`` gives them."""
    mine = [(name, max(s, start), e, args) for name, s, e, args in events
            if start < e <= opens]
    compiles = [ev for ev in mine if ev[0] in KINDS]
    inits = [ev for ev in mine if ev[0] == "setup/engine_init"]
    first = min([s for _, s, _, _ in compiles + inits], default=opens)
    by_kind = {kind: trace_reduce.union(
        [(s, e) for name, s, e, _ in compiles if KINDS[name] == kind])
        for kind in KINDS.values()}
    compiling = trace_reduce.union([(s, e) for _, s, e, _ in compiles])
    init = trace_reduce.union([(s, e) for _, s, e, _ in inits])
    # Ready: the newest instant after the newest constructor began.
    ready = max([s for name, s, _, _ in mine if name == "setup/ready"
                 and s >= max([s for _, s, _, _ in inits], default=start)],
                default=None)
    warm = [] if ready is None else trace_reduce.subtract(
        trace_reduce.subtract([(ready, opens)], compiling), init)
    parts = {
        "boot_s": first - start,
        "engine_init_s": trace_reduce.total(
            trace_reduce.subtract(init, compiling)),
        "trace_s": trace_reduce.total(by_kind["trace"]),
        "lower_s": trace_reduce.total(by_kind["lower"]),
        "compile_s": trace_reduce.total(by_kind["backend"]),
        "compiling_s": trace_reduce.total(compiling),
        "warm_s": trace_reduce.total(warm),
    }
    parts["unnamed_s"] = (opens - start) - (
        parts["boot_s"] + parts["engine_init_s"] + parts["compiling_s"]
        + parts["warm_s"])
    backends = [args for name, _, _, args in compiles
                if name == "compile/backend"]
    parts["programs"] = len(backends)
    parts["cache_misses"] = sum(1 for args in backends
                                if args.get("cache_hit") is False)
    # The per-program table is SELF time: a span less what the spans nested
    # directly inside it cover (``trace_reduce.nest``).
    table = {}
    for (name, _, _, args), row in zip(compiles, trace_reduce.nest(
            [(name, s, e) for name, s, e, _ in compiles])):
        entry = table.setdefault(program_of(args.get("fun_name")), {
            "trace": 0.0, "lower": 0.0, "backend": 0.0, "cache_hit": None})
        entry[KINDS[name]] += row["self_ns"]
        if "cache_hit" in args:
            entry["cache_hit"] = args["cache_hit"]
    slowest = sorted(table.items(), key=lambda kv: -(
        kv[1]["trace"] + kv[1]["lower"] + kv[1]["backend"]))[:top]
    parts["slowest"] = [
        [name, round(row["trace"], 4), round(row["lower"], 4),
         round(row["backend"], 4), row["cache_hit"]]
        for name, row in slowest]

    def newest(name):
        spans = [e - s for n, s, e, _ in mine if n == name]
        return spans[-1] if spans else None

    parts["to_ready_s"] = None if ready is None else ready - start
    parts["ready_to_window_s"] = None if ready is None else opens - ready
    parts["import_s"] = newest("setup/import")
    parts["first_step_s"] = newest("setup/first_step")
    return parts


def of_run(run):
    """The reduction of the run a reader was handed (``run`` is the harness's
    context, which keeps it for the other seven readers), noted once as
    ``setup_phases``; None on a program that keeps no record of its
    start-up."""
    from benchmark import harness

    rec = recorder()
    if rec is None:
        return None
    if "setup_phases" not in run:
        events = on_run_clock(rec)
        setup_s = float(run["values"]["setup_s"])
        start = run_start(events, harness._T0)
        run["setup_phases"] = reduce_setup(events, start, start + setup_s)
        harness.note(event="setup_phases", setup_s=setup_s,
                     events=len(events), dropped=rec.dropped,
                     **run["setup_phases"])
    return run["setup_phases"]


def reading(run, part):
    """``part`` of the run's reduction: what a reader returns."""
    parts = of_run(run)
    return None if parts is None else parts[part]
