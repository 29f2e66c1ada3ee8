"""From a profiler trace to numbers: device busy and idle time, time by
operation name, collective time and the part of it that nothing hides, and
the longest idle gaps by what the host was doing.

Reads the ``.xplane.pb`` the JAX profiler writes with
``jax.profiler.ProfileData`` and nothing else. Every PR computes these
numbers the same way, and ``tests/benchmark/test_trace_reduce.py`` checks the
arithmetic on a hand-built trace.

What is read, by name (``kernel_names.json`` holds the patterns, and every
``names/*.json`` under ``paths`` adds a family's: ``merge_names``):

- device planes ``/device:TPU:<n>``; on each, the line ``XLA Ops``: what the
  core executes, one operation at a time. Events nest (a ``while`` holds its
  body), so a name's time is SELF time: its duration minus what its
  children cover. The line ``Async XLA Ops`` (a copy or a collective from
  its start to its done) is read for collectives only: a transfer in flight
  is not the core being busy.
- an event's name is the text of its HLO instruction
  (``%closed_call.7 = bf16[..] custom-call(..), custom_call_target="tpu_custom_call"``).
  It is cut down to a LABEL, ``<path> <opcode> <target>``, where the path is
  the instruction's name under its parents' with the numbering dropped
  (``while/paged_decode custom-call tpu_custom_call``). The patterns match
  labels; a class of kernels is told by the kernel's own name, the last
  part of the path (``pallas_call(name=...)`` names the custom call).
- host planes: every event whose name looks like a span (``a/b``, as
  ``jax.profiler.TraceAnnotation`` writes them), whatever thread it is on.
"""

import glob
import os
import re

_HERE = os.path.dirname(os.path.abspath(__file__))
_SUFFIX = re.compile(r"[._]\d+$")


# What a names file may hold: lists that are united, and tables (class ->
# patterns) that are added to. Keys ending in ``why`` are prose.
_NAME_LISTS = ("scopes", "kernels", "movement", "collective", "control_flow")
_NAME_TABLES = ("classes",)


def merge_names(base_file):
    """``base_file`` (one of this directory's two names files) with what
    every ``names/*.json`` under ``paths`` adds to the keys it has: a list
    gains the entries it lacks, in the order the files are found; a table
    gains the entries it lacks, and an entry two files give different
    content is an error that names both files. A family's words, kernels
    and classes arrive as such a file (benchmark/README.md)."""
    from benchmark import harness

    base_path = os.path.join(_HERE, base_file)
    out = harness.load_json(base_path)
    owner = {(k, name): base_path for k in _NAME_TABLES if k in out
             for name in out[k]}
    for path in harness.find_all("names", ".json"):
        body = harness.load_json(path)
        unknown = sorted(k for k in body if not k.endswith("why")
                         and k not in _NAME_LISTS + _NAME_TABLES)
        if unknown:
            raise ValueError("{} has keys no reduction reads: {} (known: {})"
                             .format(path, unknown,
                                     _NAME_LISTS + _NAME_TABLES))
        for key in _NAME_LISTS:
            if key in out:
                out[key] += [x for x in body.get(key, [])
                             if x not in out[key]]
        for key in _NAME_TABLES:
            if key not in out:
                continue
            for name, content in body.get(key, {}).items():
                if out[key].setdefault(name, content) != content:
                    raise ValueError(
                        "{} {!r} is defined twice with different content: "
                        "in {} and in {}".format(
                            key, name, owner[key, name], path))
                owner.setdefault((key, name), path)
    return out


def kernel_names():
    """``kernel_names.json`` with every family's ``classes`` (and
    collectives) merged in."""
    return merge_names("kernel_names.json")


def find_xplane(trace_dir):
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError("no .xplane.pb under {}".format(trace_dir))
    return max(files, key=os.path.getmtime)


def load(path):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def group_name(name):
    """``fusion.123`` -> ``fusion``: the trace numbers every instruction, and
    a breakdown by instruction number repeats nothing from run to run."""
    prev = None
    while prev != name:
        prev, name = name, _SUFFIX.sub("", name)
    return name


def union(intervals):
    """Sorted, merged copy of ``[(start, end), ...]``."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """The part of merged intervals ``a`` that merged intervals ``b`` do not
    cover."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


_INSTRUCTION = re.compile(r"^%(\S+) = .*?[ )]([a-z][\w-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def parse_name(name):
    """An event's name -> (instruction name without numbering, opcode,
    custom-call target or ""). A name that is not HLO text is its own
    instruction and opcode."""
    m = _INSTRUCTION.match(name)
    if m is None:
        return group_name(name), group_name(name), ""
    target = _TARGET.search(name) if m.group(2) == "custom-call" else None
    return group_name(m.group(1)), m.group(2), target.group(1) if target \
        else ""


def nest(events):
    """``[(name, start, end)]`` of one line -> one dict an event: ``label``
    (see the module's docstring), ``opcode``, ``show`` (the short name a breakdown
    prints), ``start``, ``end``, ``self_ns`` and ``own``: the stretches of
    the event that none of its direct children covers."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    parsed = [parse_name(e[0]) for e in events]
    children = [[] for _ in events]
    paths = [None] * len(events)
    stack = []
    for i in order:
        _, s, e = events[i]
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][2]:
            children[stack[-1]].append((s, e))
            paths[i] = paths[stack[-1]] + "/" + parsed[i][0]
        else:
            paths[i] = parsed[i][0]
        stack.append(i)
    out = []
    for (_, s, e), kids, path, (instr, opcode, target) in zip(
            events, children, paths, parsed):
        own = subtract([(s, e)], union(kids)) if e > s else []
        out.append({
            "label": " ".join(x for x in (path, opcode, target) if x),
            "opcode": opcode,
            "show": instr if opcode in instr else "{} ({})".format(
                instr, opcode),
            "start": s, "end": e, "self_ns": total(own), "own": own})
    return out


def _matches(patterns, name):
    return any(re.search(p, name) for p in patterns)


def _line_events(line):
    return [(ev.name, float(ev.start_ns), float(ev.start_ns)
             + float(ev.duration_ns)) for ev in line.events]


def reduce_trace(profile, names=None, top=10):
    """The whole reduction. ``profile`` is a ``ProfileData``.

    Returns a dict: ``window_s``, ``busy_s`` and ``idle_pct`` (busy averaged
    over the chips, idle share of the worst chip), ``devices`` (one entry a
    chip), ``op_s`` (self seconds by short name, averaged over chips),
    ``classes`` (seconds by class of ``kernel_names.json``, averaged; ``class_calls``
    the number of events, with the collectives'),
    ``collective_s`` / ``collective_exposed_s`` (worst chip), ``spans``
    (host span name -> [count, seconds]) and the two lists of the
    ``breakdown``. None when no operation ran on a device."""
    names = names or kernel_names()
    dev_re = re.compile(names["device_plane"])
    line_re = re.compile(names["op_line"])
    async_re = re.compile(names["async_line"])
    span_re = re.compile(names["host_span"])

    per_device, spans = [], []
    for plane in profile.planes:
        if dev_re.search(plane.name):
            events, in_flight = [], []
            for line in plane.lines:
                if line_re.search(line.name):
                    events.extend(nest(_line_events(line)))
                elif async_re.search(line.name):
                    in_flight.extend(
                        (s, e) for name, s, e in _line_events(line)
                        if _matches(names["collective"],
                                    parse_name(name)[1]))
            if events:
                per_device.append((plane.name, events, in_flight))
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if span_re.search(ev.name):
                        s = float(ev.start_ns)
                        spans.append((ev.name, s, s + float(ev.duration_ns)))
    if not per_device:
        return None

    # The traced window: the benchmark's own span where the clocks agree
    # with the device's, else the extent of the device's events.
    lo = min(e["start"] for _, evs, _ in per_device for e in evs)
    hi = max(e["end"] for _, evs, _ in per_device for e in evs)
    window_from = "device_events"
    for name, s, e in spans:
        if name == names["window_span"] and s <= lo and e >= hi:
            lo, hi, window_from = s, e, name
    window = hi - lo

    devices, op_s, class_s, class_n = [], {}, {}, {}
    n = float(len(per_device))
    for plane_name, events, in_flight in per_device:
        busy = union([(e["start"], e["end"]) for e in events])
        is_coll = [_matches(names["collective"], e["opcode"])
                   for e in events]
        coll = union(in_flight + [(e["start"], e["end"])
                                  for e, c in zip(events, is_coll) if c])
        # What could hide a collective: another operation's SELF time (a
        # parent such as `while` works only where no child of its own is
        # listed).
        other = union([seg for e, c in zip(events, is_coll) if not c
                       and not _matches(names["control_flow"], e["opcode"])
                       for seg in e["own"]])
        exposed = subtract(coll, other)
        for e in events:
            op_s[e["show"]] = op_s.get(e["show"], 0.0) \
                + e["self_ns"] / 1e9 / n
            for cls, pats in names["classes"].items():
                if _matches(pats, e["label"]):
                    class_s[cls] = class_s.get(cls, 0.0) \
                        + e["self_ns"] / 1e9 / n
                    class_n[cls] = class_n.get(cls, 0.0) + 1 / n
        class_n["collective"] = class_n.get("collective", 0.0) + (
            sum(is_coll) + len(in_flight)) / n
        devices.append({
            "plane": plane_name, "busy_s": total(busy) / 1e9,
            "idle_pct": 100.0 * (1.0 - total(busy) / window),
            "collective_s": total(coll) / 1e9,
            "collective_exposed_s": total(exposed) / 1e9,
            "_busy": busy})

    worst = max(devices, key=lambda d: d["idle_pct"])
    gaps = subtract([(lo, hi)], worst["_busy"])
    by_span = {}
    leaf_first = sorted((s for s in spans if s[0] != names["window_span"]),
                        key=lambda s: s[2] - s[1])
    for gs, ge in gaps:
        owner, best = "(no span)", 0.0
        for name, s, e in leaf_first:
            ov = min(e, ge) - max(s, gs)
            if ov >= 0.5 * (ge - gs):
                owner = name
                break
            if ov > best:
                owner, best = name, ov
        by_span[owner] = by_span.get(owner, 0.0) + (ge - gs) / 1e9
    span_stats = {}
    for name, s, e in spans:
        c = span_stats.setdefault(name, [0, 0.0])
        c[0] += 1
        c[1] += (e - s) / 1e9
    for d in devices:
        del d["_busy"]

    def ranked(table):
        return [[k, v] for k, v in sorted(table.items(),
                                          key=lambda kv: -kv[1])[:top]]

    return {
        "window_s": window / 1e9, "window_from": window_from,
        "busy_s": sum(d["busy_s"] for d in devices) / n,
        "idle_pct": worst["idle_pct"],
        "devices": devices, "op_s": op_s, "classes": class_s,
        "class_calls": class_n,
        "collective_s": max(d["collective_s"] for d in devices),
        "collective_exposed_s": max(d["collective_exposed_s"]
                                    for d in devices),
        "spans": span_stats,
        "device_ops": ranked(op_s), "idle_gaps": ranked(by_span),
    }


def describe(profile, per_line=12):
    """What a trace holds, for a person to read before writing patterns:
    planes, lines, and on each line the names that took most time with one
    event's stats."""
    out = []
    for plane in profile.planes:
        lines = []
        for line in plane.lines:
            by_name, sample = {}, {}
            count = 0
            for ev in line.events:
                count += 1
                g = group_name(ev.name)
                by_name[g] = by_name.get(g, 0.0) + float(ev.duration_ns)
                if g not in sample:
                    try:
                        sample[g] = {str(k): str(v)[:160]
                                     for k, v in ev.stats}
                    except Exception as e:  # stats are optional decoration
                        sample[g] = {"stats_error": repr(e)}
                    sample[g]["_name"] = ev.name
            top = sorted(by_name.items(), key=lambda kv: -kv[1])[:per_line]
            lines.append({"line": line.name, "events": count,
                          "top": [[k, v / 1e9, sample[k]] for k, v in top]})
        out.append({"plane": plane.name, "lines": lines})
    return out
