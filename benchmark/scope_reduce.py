"""From a profiler trace to the program's own names: device self time by
region of a step (``jax.named_scope``), by kernel (with call counts) and by
operation inside a region; host time by the engines' spans; and each
request's transitions with its ``rid``.

``trace_reduce.py`` tells operations apart by the names XLA invented and by
where they nest. This module reads the names the PROGRAM gave (PERF.md
section 3): a region's words sit in the ``op_name`` of every operation
traced under it, a kernel's name is its custom call's instruction name on
the chip and a scope of its interpreted operations off it, a span is a
``jax.profiler.TraceAnnotation`` under the name the ring has too.

How an operation's ``op_name`` is found, the same way on the chip and on
the CPU: a device event names its HLO instruction (the chip's event name is
the instruction's text, the CPU's carries ``hlo_op``) and its program (stat
``program_id``; where an event has none, the module event on the chip's
module line that covers it); the ``.xplane.pb`` embeds the optimised HLO
of every program that ran (plane ``/host:metadata``, one ``Hlo Proto`` an
executable), whose instructions carry ``metadata.op_name``.
``jax.profiler.ProfileData`` does not expose that plane's bytes, so the
few fields needed are read from the file's wire format here (``fields``).
The profiler leaves one program out, the four-chip SPMD train step (my
chip run, PR 23): for a module the file does not embed, the table the
program keeps of the steps it analysed (``telemetry/xray.py`` ``OP_NAMES``,
filled by ``engine.perf_xray()``, which the train driver calls in a traced
run) gives the same instruction -> ``op_name``.
An operation the compiler inserted (a layout copy) has no ``op_name``: it
takes the region of the event it nests in and is counted as ``inherited``;
with no such parent it is ``(unscoped)``.

A parent commit has none of the names: every table is then empty or
``(unscoped)``, nothing raises, and a reader returns nothing.
"""

import os
import re

from benchmark import trace_reduce

_WRAPPER = re.compile(r"^[\w.-]+\((.*)\)$")
UNSCOPED = "(unscoped)"


def scope_names():
    """``scope_names.json`` with every family's region words, kernel names
    and movement opcodes merged in (``trace_reduce.merge_names``);
    ``kernel`` is the expression that finds a kernel: one of the ``kernels``
    at the start of a name (``flash_bwd`` finds ``flash_bwd_fused``)."""
    names = trace_reduce.merge_names("scope_names.json")
    names["kernel"] = "^({})".format(
        "|".join(re.escape(k) for k in names["kernels"]))
    return names


# ---------------------------------------------------------------------------
# The protobuf wire format, as far as it is needed: a message is a run of
# (field number, wire type, value); nested messages, strings and bytes are
# length-delimited (type 2).
# ---------------------------------------------------------------------------

def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def fields(buf):
    """``(field number, value)`` of every field of the message in ``buf``:
    an int for a varint, a ``memoryview`` for anything with a length or a
    fixed width."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError("wire type {} at byte {}".format(kind, i))
        yield key >> 3, value


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def _hlo_instructions(hlo_proto):
    """``HloProto`` bytes -> (module name, {instruction name: (opcode,
    op_name)}). Field numbers: HloProto.hlo_module 1; HloModuleProto.name 1,
    .computations 3; HloComputationProto.instructions 2;
    HloInstructionProto.name 1, .opcode 2, .metadata 7; OpMetadata.op_name
    2 (xla/service/hlo.proto, xla/xla_data.proto)."""
    module_name, ops = "", {}
    for n, module in fields(hlo_proto):
        if n != 1:
            continue
        for n1, v1 in fields(module):
            if n1 == 1:
                module_name = _text(v1)
            elif n1 == 3:
                for n2, instruction in fields(v1):
                    if n2 != 2:
                        continue
                    name = opcode = op_name = ""
                    for n3, v3 in fields(instruction):
                        if n3 == 1:
                            name = _text(v3)
                        elif n3 == 2:
                            opcode = _text(v3)
                        elif n3 == 7:
                            for n4, v4 in fields(v3):
                                if n4 == 2:
                                    op_name = _text(v4)
                    ops[name] = (opcode, op_name)
    return module_name, ops


def embedded_hlo(path, names=None):
    """The programs whose HLO the ``.xplane.pb`` at ``path`` embeds:
    ``{program id: (module name, {instruction: (opcode, op_name)})}``.
    Field numbers: XSpace.planes 1; XPlane.name 2, .event_metadata 4 and
    .stat_metadata 5 (maps: key 1, value 2); XEventMetadata.id 1, .stats 5;
    XStatMetadata.name 2; XStat.metadata_id 1, .bytes_value 6
    (tsl/profiler/protobuf/xplane.proto)."""
    names = names or scope_names()
    with open(path, "rb") as f:
        space = memoryview(f.read())
    programs = {}
    for n, plane in fields(space):
        if n != 1:
            continue
        plane_name, events, stat_names = "", [], {}
        for n1, v1 in fields(plane):
            if n1 == 2:
                plane_name = _text(v1)
                if plane_name != names["metadata_plane"]:
                    break
            elif n1 in (4, 5):
                key, value = None, None
                for n2, v2 in fields(v1):
                    if n2 == 1:
                        key = v2
                    elif n2 == 2:
                        value = v2
                if n1 == 4:
                    events.append((key, value))
                else:
                    stat_names[key] = "".join(
                        _text(v) for k, v in fields(value) if k == 2)
        if plane_name != names["metadata_plane"]:
            continue
        for program_id, meta in events:
            for n2, stat in fields(meta):
                if n2 != 5:
                    continue
                stat_id, blob = None, None
                for n3, v3 in fields(stat):
                    if n3 == 1:
                        stat_id = v3
                    elif n3 == 6:
                        blob = v3
                if blob is not None and \
                        stat_names.get(stat_id) == names["hlo_stat"]:
                    programs[program_id] = _hlo_instructions(blob)
    return programs


# ---------------------------------------------------------------------------
# Names
# ---------------------------------------------------------------------------

def components(op_name):
    """``jit(train_step)/transpose(jvp(lm_head))/dot_general`` ->
    ``[train_step, lm_head, dot_general]``: the parts of an ``op_name``
    with JAX's transform wrappers taken off."""
    out = []
    for part in op_name.split("/"):
        m = _WRAPPER.match(part)
        while m is not None:
            part = m.group(1)
            m = _WRAPPER.match(part)
        out.append(part)
    return out


def scope_path(parts, words):
    """The region of an operation: the ``parts`` that are scope words, in
    order, an immediately repeated word once; ``""`` when there is none."""
    out = []
    for part in parts:
        if part in words and (not out or out[-1] != part):
            out.append(part)
    return "/".join(out)


def kernel_name(parts, instruction, kernel_re):
    """(kernel, call site) of an operation: the kernel is a part of its
    ``op_name`` that is a kernel's name, and the site the ``op_name`` up to
    that part (one site a layer); else its own instruction name if that is
    a kernel's (the chip names the custom call after the kernel); else
    (None, None)."""
    for i, part in enumerate(parts):
        if kernel_re.match(part):
            return part, "/".join(parts[:i + 1])
    base = trace_reduce.group_name(instruction)
    return (base, instruction) if kernel_re.match(base) else (None, None)


def is_movement(show, opcode, movement):
    """Pure data movement: one of the ``movement`` opcodes, or a fusion
    named after nothing else (``slice_bitcast_fusion``)."""
    if opcode in movement:
        return True
    if opcode != "fusion":
        return False
    words = show.split(" ")[0].split("_")
    return len(words) > 1 and words[-1] == "fusion" and \
        all(w in movement for w in words[:-1])


# ---------------------------------------------------------------------------
# The reduction
# ---------------------------------------------------------------------------

def _stats(ev):
    try:
        return {str(k): v for k, v in ev.stats}
    except Exception:  # stats are optional decoration
        return {}


def _parents(events):
    """Index of the innermost event that contains each of ``[(name, start,
    end)]`` (None at top level): the walk ``trace_reduce.nest`` makes."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    parent, stack = [None] * len(events), []
    for i in order:
        _, s, e = events[i]
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][2]:
            parent[i] = stack[-1]
        stack.append(i)
    return parent, order


def _module_of(stats, modules, start):
    """The program an event belongs to: its own ``program_id`` stat, else
    the module event (``[(start, end, program id)]``, sorted) covering its
    start."""
    if "program_id" in stats:
        return int(stats["program_id"])
    for s, e, program in modules:
        if s <= start < e:
            return program
    return None


_MODULE_EVENT = re.compile(r"^(.*)\((\d+)\)$")


def _known(programs, module, program_id):
    """True once ``programs`` holds the module: from the trace's embedded
    HLO, else (the profiler leaves the four-chip step out) from the table
    the program's own analysis of its compiled steps keeps
    (``telemetry/xray.py`` ``OP_NAMES``; a program without one has none)."""
    if program_id not in programs:
        from deepspeed_tpu.telemetry import xray

        table = getattr(xray, "OP_NAMES", {}).get(module)
        if table is None:
            return False
        programs[program_id] = (module, {
            name: ("", op_name) for name, op_name in table.items()})
    return True


def reduce_scopes(path, planes, names=None, trace_names=None):
    """The whole reduction of the ``.xplane.pb`` at ``path``.

    ``planes`` are the device planes the old reduction found
    (``trace["devices"][i]["plane"]``); device tables are averaged over
    them, as ``trace_reduce`` averages. Returns a dict:

    - ``scope_s``: region -> device self seconds (``(unscoped)`` for none);
      ``inherited_s``: the part of it that compiler-inserted operations
      took from the event they nest in;
    - ``scope_ops``: region -> {short operation name: self seconds};
    - ``kernels``: kernel name -> {``s``: self seconds, ``calls``: calls};
    - ``move_scan_s``: self seconds of pure data movement inside the
      decode scan (by region, or, where nothing has a region, by nesting
      under a top-level ``while``); ``move_outside_s``: the same outside it
      (the layout copies where a serving step enters and leaves);
    - ``named_s``: self seconds under any region or kernel name;
    - ``regions``: the scope words the embedded programs hold at all (a
      region that did not run reads 0, one the program lacks reads nothing);
    - ``host``: span name -> {``count``, ``total_s``, ``self_s``};
    - ``instants``: request instant name -> [(rid, seconds, stats)].
    """
    names = names or scope_names()
    trace_names = trace_names or trace_reduce.kernel_names()
    programs = embedded_hlo(path, names)
    reduction = _Reduction(names, programs, len(planes))
    op_lines = [re.compile(trace_names["op_line"]),
                re.compile(names["cpu"]["op_line"])]
    module_line = re.compile(names["module_line"])
    for plane in trace_reduce.load(path).planes:
        if plane.name in planes:
            modules = sorted(
                (float(ev.start_ns), float(ev.start_ns)
                 + float(ev.duration_ns), int(m.group(2)))
                for line in plane.lines if module_line.search(line.name)
                for ev in line.events
                for m in [_MODULE_EVENT.search(ev.name)]
                if m and _known(programs, m.group(1), int(m.group(2))))
            for line in plane.lines:
                if any(r.search(line.name) for r in op_lines):
                    reduction.device_line(line, modules)
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                reduction.host_line(line)
    return reduction.result()


class _Reduction(object):
    """The names to look for, the embedded programs, and the tables one
    trace's lines add up to."""

    def __init__(self, names, programs, chips):
        self.words = set(names["scopes"])
        self.kernel_re = re.compile(names["kernel"])
        self.movement = set(names["movement"])
        self.scan = names["scan"]
        self.host_re = re.compile(names["host_spans"])
        self.instant_re = re.compile(names["request_instants"])
        self.programs = programs
        self.chips = float(chips)
        self.scope_s, self.inherited_s, self.scope_ops = {}, {}, {}
        self.kernels, self.host, self.instants = {}, {}, {}
        self.move_scan_s = self.move_outside_s = self.named_s = 0.0

    def result(self):
        for k in self.kernels.values():
            # Interpreted: a call runs each of the kernel's outermost
            # operations once, so a site's most frequent one counts its calls.
            sites = k.pop("_sites")
            k["calls"] = k.pop("_events") or sum(
                max(runs.values()) for runs in sites.values())
        regions = sorted({part for _, ops in self.programs.values()
                          for _, op_name in ops.values() if op_name
                          for part in components(op_name)
                          if part in self.words})
        return {"scope_s": self.scope_s, "inherited_s": self.inherited_s,
                "scope_ops": self.scope_ops, "kernels": self.kernels,
                "move_scan_s": self.move_scan_s,
                "move_outside_s": self.move_outside_s,
                "named_s": self.named_s,
                "regions": regions, "host": self.host,
                "instants": self.instants}

    def device_line(self, line, modules):
        raw, stats = [], []
        for ev in line.events:
            start = float(ev.start_ns)
            raw.append((ev.name, start, start + float(ev.duration_ns)))
            stats.append(_stats(ev))
        if not raw:
            return
        nested = trace_reduce.nest(raw)
        parent, order = _parents(raw)
        # the chip's event name is its instruction's text, the CPU's a stat
        instruction = [stats[i].get("hlo_op")
                       or raw[i][0].split(" ", 1)[0].lstrip("%")
                       for i in range(len(raw))]
        region = [""] * len(raw)
        kernel, site = [None] * len(raw), [None] * len(raw)
        for i in order:  # parents come before their children
            program = self.programs.get(
                _module_of(stats[i], modules, raw[i][1]))
            op_name = program[1].get(instruction[i], ("", ""))[1] \
                if program else ""
            parts = components(op_name) if op_name else []
            kernel[i], site[i] = kernel_name(parts, instruction[i],
                                             self.kernel_re)
            region[i] = scope_path(parts, self.words)
            up = parent[i]
            if not region[i] and up is not None and region[up]:
                region[i] = region[up]
                self.inherited_s[region[i]] = \
                    self.inherited_s.get(region[i], 0.0) \
                    + nested[i]["self_ns"] / 1e9 / self.chips
        # The scan is told by nesting on a line where NO event has a region.
        any_region = any(region)
        for i, e in enumerate(nested):
            s = e["self_ns"] / 1e9 / self.chips
            key = region[i] or UNSCOPED
            self.scope_s[key] = self.scope_s.get(key, 0.0) + s
            ops = self.scope_ops.setdefault(key, {})
            ops[e["show"]] = ops.get(e["show"], 0.0) + s
            if region[i] or kernel[i]:
                self.named_s += s
            if kernel[i] is not None:
                self._count_kernel(kernel, site, parent, instruction, i, e, s)
            path = e["label"].split(" ")[0].split("/")
            in_scan = self.scan in region[i].split("/") if any_region \
                else (path[0] == "while" and len(path) > 1)
            if is_movement(e["show"], e["opcode"], self.movement):
                if in_scan:
                    self.move_scan_s += s
                else:
                    self.move_outside_s += s

    def _count_kernel(self, kernel, site, parent, instruction, i, e, s):
        k = self.kernels.setdefault(
            kernel[i], {"s": 0.0, "_events": 0.0, "_sites": {}})
        k["s"] += s
        up = parent[i]
        if e["opcode"] == "custom-call":
            k["_events"] += 1 / self.chips  # the chip: one event a call
        elif up is None or kernel[up] != kernel[i]:
            runs = k["_sites"].setdefault(site[i], {})
            runs[instruction[i]] = runs.get(instruction[i], 0.0) \
                + 1 / self.chips

    def host_line(self, line):
        spans = []
        for ev in line.events:
            if self.instant_re.search(ev.name):
                stats = _stats(ev)
                if "rid" in stats:
                    self.instants.setdefault(ev.name, []).append(
                        (int(stats["rid"]), float(ev.start_ns) / 1e9, stats))
            elif self.host_re.search(ev.name):
                start = float(ev.start_ns)
                spans.append((ev.name, start,
                              start + float(ev.duration_ns)))
        # Self time among the program's own spans: a span minus its children.
        for (name, s, e), nested in zip(spans, trace_reduce.nest(spans)):
            row = self.host.setdefault(name, {"count": 0, "total_s": 0.0,
                                              "self_s": 0.0})
            row["count"] += 1
            row["total_s"] += (e - s) / 1e9
            row["self_s"] += nested["self_ns"] / 1e9


# ---------------------------------------------------------------------------
# What the readers share
# ---------------------------------------------------------------------------

def under(table, word):
    """Seconds of ``table`` (region -> seconds) in regions that hold
    ``word``: ``lm_head`` takes ``lm_head`` and ``block/../lm_head``."""
    return sum(s for region, s in table.items()
               if word in region.split("/"))


def kernel_total(kernels, prefix):
    """(self seconds, calls) of the kernels whose name starts with
    ``prefix``."""
    rows = [k for name, k in kernels.items() if name.startswith(prefix)]
    return sum(k["s"] for k in rows), sum(k["calls"] for k in rows)


def flash_roofline_pct(run, which, calls, measured):
    """Least time of ``calls`` flash ``which`` (``fwd`` / ``bwd``) passes at
    the cell's shapes (benchmark/costs.py) over ``measured`` seconds."""
    from benchmark import costs

    if not measured or not calls:
        return None
    c = run["counters"]
    cost = costs.flash_attention_cost(
        c["global_batch"] // c["chips"], c["n_head"], c["seq_len"],
        c["head_dim"])
    least = costs.least_seconds(cost[which + "_flops"],
                                cost[which + "_bytes"],
                                costs.device_peaks(run["device"]["kind"]))[0]
    return 100.0 * least * calls / measured


def request_gaps_ms(instants, earlier, later, carried):
    """One sample a request the trace SAW pass ``later``, in milliseconds
    from its ``earlier`` instant: both on the profiler's clock where the
    trace holds both, else the reading the program put on the request's
    instants (stat ``carried``, on every instant after ``later``) when a
    transition fell before the traced window. A request that has not
    reached ``later`` gives no sample."""
    first, second, read = {}, {}, {}
    for rid, t, _ in instants.get(earlier, []):
        first.setdefault(rid, t)
    for rid, t, _ in instants.get(later, []):
        second.setdefault(rid, t)
    for rows in instants.values():
        for rid, _, stats in rows:
            if carried in stats:
                read[rid] = float(stats[carried])
    out = []
    for rid in sorted(set(second) | set(read)):
        if rid in first and rid in second and first[rid] <= second[rid]:
            out.append((second[rid] - first[rid]) * 1e3)
        elif rid in read:
            out.append(read[rid])
    return out


def host_ms_a_step(host, spans, step):
    """Self milliseconds of the ``spans`` for each ``step`` span; None when
    the trace holds no ``step`` span (a program without them)."""
    steps = host.get(step, {}).get("count", 0)
    if not steps:
        return None
    return 1e3 * sum(host.get(s, {}).get("self_s", 0.0)
                     for s in spans) / steps


def region_pct(run, word):
    """Device self time under the region ``word`` over device busy time;
    None for a program that has no such region, 0 where it did not run."""
    reduced = of_run(run)
    if word not in reduced["regions"]:
        return None
    return 100.0 * under(reduced["scope_s"], word) / run["trace"]["busy_s"]


def request_gap_p50_ms(run, earlier, later, carried):
    """Median of ``request_gaps_ms`` over the traced tail, with the samples
    noted in the run's log (a tail holds a handful); None without one."""
    import statistics

    from benchmark import harness

    gaps = request_gaps_ms(of_run(run)["instants"], earlier, later, carried)
    harness.note(event="request_gaps", earlier=earlier, later=later,
                 samples=len(gaps), ms=gaps)
    return statistics.median(gaps) if gaps else None


# One reduction a process: every reader of a traced run shares it.
_CACHE = {}


def of_run(run):
    """The reduction of the traced run a reader was handed (``run`` is the
    harness's context), found as the harness finds the trace:
    ``OUT_DIR/trace/<cell>``."""
    from benchmark import harness

    path = trace_reduce.find_xplane(
        os.path.join(harness.OUT_DIR, "trace", run["cell"].name))
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()
        reduced = _CACHE[key] = reduce_scopes(
            path, [d["plane"] for d in run["trace"]["devices"]])
        # One of the run's earlier lines: where the time went by the
        # program's own names (PERF.md section 5 is written from it).
        harness.note(event="regions", scope_s=reduced["scope_s"],
                     inherited_s=reduced["inherited_s"],
                     kernels=reduced["kernels"], host=reduced["host"],
                     named_s=reduced["named_s"],
                     move_scan_s=reduced["move_scan_s"],
                     move_outside_s=reduced["move_outside_s"])
    return _CACHE[key]
