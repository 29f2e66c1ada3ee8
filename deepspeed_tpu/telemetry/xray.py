"""Perf X-ray: the compiled-program cost/memory observatory.

The serving and training engines hold a handful of jitted programs whose
identity is already a contract (the zero-recompile guarantee, the
@hot_path allowlist in analysis/annotations.py) — but until this module
nothing recorded what those programs *cost*. XLA knows: every
``Compiled`` executable carries ``cost_analysis()`` (flops, bytes
accessed) and ``memory_analysis()`` (argument/output/temp split), both
computed at compile time and therefore available on ANY backend — a
CPU-only round banks the same cost-model numbers a TPU round would.

Three pieces:

- ``ProgramRegistry``: per-program records keyed on (label, shape
  signature). Call sites ``stash()`` the live call's arguments — leaves
  are converted to ``jax.ShapeDtypeStruct`` immediately, so nothing
  retains a donated buffer — and the expensive part (an AOT
  ``lower().compile()`` of the SAME program, which never touches the jit
  wrapper's ``_cache_size()`` and therefore can never register as a
  recompile) is deferred to ``materialize()``, which export paths call.
  Steady-state per-step cost is one signature tuple + a dict compare.
  Each record holds the HLO fingerprint (sha256 of the lowered text),
  input shapes/static args, flops, bytes accessed, the peak-HBM split,
  and the donation map. A genuinely NEW signature under the same label
  is a program-identity change: ``RecompileDetector`` warnings and the
  autopsy both name it through ``identity()`` / ``recompile_dicts()``.
  A signature seen before (a label alternating between shapes it has
  already compiled) only flips the active pointer — it is in the jit
  cache already, so nothing accumulates and nothing logs.

- Roofline gauges: per-program ``xray_mfu`` / ``xray_mbu`` /
  ``xray_roofline_ratio`` from cost-model flops ÷ sampled step wall
  time against the device's ``DEVICE_PEAKS`` row. The CPU has none: it
  publishes the cost facts with ``platform="cpu"`` labels and NO
  utilization gauges — a fabricated MFU is worse than none.

- Step-time decomposition: ``observe_step(label, dispatch_s, wait_s)``
  takes, on EVERY step, the two durations the engine already has: its
  span around the dispatch (host schedule) and its span around the
  harvest, which blocks on the step's outputs anyway (device wait). No
  sync of its own; their sum is the only measured seconds the roofline
  uses.

``HBMLedger`` reconciles predicted HBM (params + KV arena + program
temp) against live ``device.memory_stats()`` where the backend has it,
and ``cost_model_gate`` compares two ``perf_xray`` report sections so
the regression gate flags cost-model deltas without hardware.

Importing this module must succeed on a bare interpreter: jax is
imported lazily inside the functions that need it.
"""

import hashlib
import re
import threading
import time
from itertools import chain as _chain

from deepspeed_tpu.utils.logging import logger

# Version stamp of the ``perf_xray`` artifact section. Bump on any
# field rename/removal; the gate refuses to compare across versions.
SCHEMA_VERSION = 1

# How a compiled Pallas TPU kernel and the collectives read in the
# optimized HLO text (``compiled.as_text()``).
_KERNEL_CALL = 'custom_call_target="tpu_custom_call"'
_COLLECTIVE_OPS = ("all-reduce", "reduce-scatter", "all-gather",
                   "all-to-all", "collective-permute")

# ``{module name: {instruction name: op_name}}`` of every program an export
# analysed, from its optimised HLO text: the ``jax.named_scope`` path of each
# instruction. Process-wide, for a reader of a profiler trace whose file
# does not embed the program (the profiler leaves out the four-chip SPMD
# step; benchmark/scope_reduce.py looks here then).
OP_NAMES = {}
_HLO_MODULE = re.compile(r"^HloModule ([\w.\-]+)")
_HLO_OP_NAME = re.compile(
    r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?\bop_name="([^"]*)"', re.M)


def _record_op_names(text):
    module = _HLO_MODULE.match(text)
    if module is not None:
        OP_NAMES[module.group(1)] = dict(_HLO_OP_NAME.findall(text))


# Bound on retained recompile events: a genuine recompile loop must not
# grow the autopsy (or the registry) without bound. Overflow is counted
# in ``recompile_events_dropped``, never silent.
RECOMPILE_EVENT_CAP = 64

# Peak compute / memory bandwidth per chip, keyed by the string the
# device reports as ``jax.devices()[0].device_kind``. This is the ONE
# peaks table: the roofline gauges here and benchmark/costs.py read it.
# A device kind that is not in the table is an error where a utilization
# is asked for (``device_peaks``) — no chip is given another chip's row.
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,       # bf16
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, \"TPU v5e\": 197 TFLOP/s "
                  "bf16, 16 GB HBM at 819 GB/s per chip",
    },
}


def device_peaks(device_kind):
    """The peaks row for ``device_kind``; an unknown kind raises."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            "no peaks row for device kind {!r} (known: {}); add it to "
            "telemetry.xray.DEVICE_PEAKS with its source before asking "
            "for a utilization".format(
                device_kind, sorted(DEVICE_PEAKS))) from None


_tree_leaves_fn = None


def _tree_leaves(tree):
    global _tree_leaves_fn
    f = _tree_leaves_fn
    if f is None:
        from jax.tree_util import tree_leaves as f

        _tree_leaves_fn = f
    return f(tree)


# str(dtype) memo: dtype objects are interned per process, and the
# conversion is the dominant per-leaf cost on a ~50-leaf params tree
# (the signature is paid EVERY step — the overhead gate in
# tests/unit/test_telemetry_overhead.py holds it under 5% of a tiny-
# model CPU step).
_DTYPE_STRS = {}


def _sig_leaf(leaf):
    dt = getattr(leaf, "dtype", None)
    if dt is not None:
        s = _DTYPE_STRS.get(dt)
        if s is None:
            s = _DTYPE_STRS[dt] = str(dt)
        return (tuple(leaf.shape), s)
    return ("static", type(leaf).__name__, repr(leaf)[:80])


def _signature(args, kwargs):
    """Cheap structural signature of a call: (shape, dtype) per array
    leaf, (type, repr) per static leaf. This is the per-step cost of
    the observatory — tens of microseconds, no device touch."""
    return tuple(map(_sig_leaf, _tree_leaves((args, kwargs))))


def _abstractify(tree):
    """Replace every array leaf with a ShapeDtypeStruct so a stash
    retains shapes, never buffers — the engine donates its pool into
    the very programs being observed. A committed array keeps its
    sharding: the AOT analysis must lower the program the call ran
    (ZeRO-sharded state, a replica pinned to its chip), and a donated
    sharded argument lowered as unsharded cannot even alias its
    output."""
    import jax
    import numpy as np

    def conv(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            sharding = x.sharding if getattr(x, "committed", False) else None
            return jax.ShapeDtypeStruct(tuple(x.shape), np.dtype(x.dtype),
                                        sharding=sharding)
        return x

    return jax.tree_util.tree_map(conv, tree)


def _shapes_of(sig):
    """Human form of a signature: dynamic leaves as ``int32[1,16]``,
    static leaves as their type name."""
    out = []
    for entry in sig:
        if entry[0] == "static":
            out.append("static:{}".format(entry[1]))
        else:
            shape, dtype = entry
            out.append("{}[{}]".format(
                dtype, ",".join(str(d) for d in shape)))
    return out


class _Stash(object):
    """One (label, signature) capture: abstract args now, compiled
    analysis later (``record`` is filled by materialize()). ``calls``/
    ``tokens`` accumulate the note() accounting for the steps this
    signature was active — cost attribution stays per-signature even
    when a label cycles through several."""

    __slots__ = ("label", "sig", "jitted", "args", "kwargs", "donate",
                 "record", "calls", "tokens", "facts")

    def __init__(self, label, sig, jitted, args, kwargs, donate):
        self.label = label
        self.sig = sig
        self.jitted = jitted
        self.args = args
        self.kwargs = kwargs
        self.donate = tuple(donate)
        self.record = None
        self.calls = 0
        self.tokens = 0
        self.facts = {}


def _public_event(ev):
    """A recompile event minus its internal stash references."""
    return {k: v for k, v in ev.items() if not k.startswith("_")}


class ProgramRegistry(object):
    """The observatory. ``registry`` is a MetricsRegistry (or None for
    a private, unpublished instance — the flops profiler's mode);
    ``platform`` is a jax backend name (detected lazily when omitted);
    ``peaks`` overrides the DEVICE_PEAKS row."""

    def __init__(self, registry=None, platform=None, peaks=None):
        self._registry = registry
        self._platform = platform
        self._peaks_override = peaks
        self._lock = threading.Lock()
        self._programs = {}      # label -> [stash, ...] (insertion order)
        self._sig_index = {}     # label -> {sig: stash}
        self._active_sig = {}    # label -> signature tuple
        self._active = {}        # label -> active stash
        self._prev_active = {}   # label -> previously active stash
        self._active_parts = {}  # label -> per-arg parts (fast path)
        self._sig_memo = {}      # label -> [(arg, parts) | None, ...]
        self._pending = {}       # label -> [calls, tokens] pre-stash
        self._step_s = {}        # label -> EWMA observed step seconds
        self._decomp = {}        # label -> [n, host_sum, wait_sum]
        self._gauged = set()     # labels with published gauges
        self._analysis = {}      # (id(jitted), sig) -> analysis dict
        # Program-identity changes flagged by a call site (the engine
        # passes track_change=detector.warm, so a program's expected
        # first compile never lands here; an already-seen signature
        # never lands here either — it is in the jit cache, so a flip
        # back to it is not a recompile). Fingerprints fill lazily at
        # materialize() — the shapes are exact from the stash itself.
        self.recompile_events = []
        self.recompile_events_dropped = 0

    # ------------------------------------------------------- hot path

    def seen(self, label):
        return label in self._active_sig

    def _arg_parts(self, label, args):
        """Per-argument signature parts, memoized on argument identity
        (``is``, not ``id()`` — each memo slot keeps a reference to the
        object it signed, so a recycled address can never alias). The
        flattened concatenation equals ``_signature(args, {})``."""
        memo = self._sig_memo.get(label)
        if memo is None or len(memo) != len(args):
            memo = self._sig_memo[label] = [None] * len(args)
        parts = [None] * len(args)
        for i, a in enumerate(args):
            m = memo[i]
            if m is not None and m[0] is a:
                parts[i] = m[1]
            else:
                if hasattr(a, "dtype") and hasattr(a, "shape"):
                    p = (_sig_leaf(a),)  # array: its own single leaf
                else:
                    p = tuple(map(_sig_leaf, _tree_leaves(a)))
                memo[i] = (a, p)
                parts[i] = p
        return tuple(parts)

    def stash(self, label, jitted, *args, donate=(), track_change=False,
              **kwargs):
        """Capture one call's program identity. Returns True when the
        label's ACTIVE signature changed (first stash included).

        A signature already seen under this label (a program
        alternating between shapes it has compiled) only switches the
        active pointer: the program is in the jit cache, so nothing is
        appended and no recompile event is logged — only a genuinely
        NEW signature captures a stash, and only a new one with
        ``track_change`` set records a recompile event (bounded by
        RECOMPILE_EVENT_CAP; overflow counts as
        ``recompile_events_dropped``).

        ``donate`` (names of donated arguments) and ``track_change``
        are reserved keyword-only options, never forwarded to the
        program; a profiled program whose own kwargs use these names
        must pre-bind them (``functools.partial``)."""
        parts = None
        if not kwargs:
            # Steady-state fast path: signature parts memoized by arg
            # identity. Long-lived args (the params tree — most of the
            # leaves) are the same objects every step, so only fresh
            # objects (the donated pool result, per-step scalars) are
            # re-walked. Holding the previous objects is free: donated
            # buffers are already invalidated, scalars are tiny.
            parts = self._arg_parts(label, args)
            if self._active_parts.get(label) == parts:
                return False
            sig = tuple(_chain.from_iterable(parts))
        else:
            sig = _signature(args, kwargs)
        if self._active_sig.get(label) == sig:
            if parts is not None:
                self._active_parts[label] = parts
            return False
        with self._lock:
            if self._active_sig.get(label) == sig:
                if parts is not None:
                    self._active_parts[label] = parts
                return False
            by_sig = self._sig_index.setdefault(label, {})
            old = self._active.get(label)
            stash = by_sig.get(sig)
            is_new = stash is None
            if is_new:
                a_args, a_kwargs = _abstractify((args, kwargs))
                stash = _Stash(label, sig, jitted, a_args, a_kwargs,
                               donate)
                pend = self._pending.pop(label, None)
                if pend is not None:
                    stash.calls, stash.tokens = pend
                by_sig[sig] = stash
                self._programs.setdefault(label, []).append(stash)
            self._active_sig[label] = sig
            self._active[label] = stash
            if old is not None and old is not stash:
                self._prev_active[label] = old
            if parts is not None:
                self._active_parts[label] = parts
            else:
                self._active_parts.pop(label, None)
            if is_new and old is not None and track_change:
                if len(self.recompile_events) >= RECOMPILE_EVENT_CAP:
                    self.recompile_events_dropped += 1
                else:
                    self.recompile_events.append({
                        "program": label,
                        "old_fingerprint": (old.record or {}).get(
                            "fingerprint"),
                        "new_fingerprint": None,
                        "old_shapes": _shapes_of(old.sig),
                        "new_shapes": _shapes_of(sig),
                        # Stash refs (stripped on export) let
                        # materialize() resolve fingerprints exactly.
                        "_old": old,
                        "_new": stash,
                    })
        return True

    def note(self, label, tokens=0, **facts):
        """Per-step accounting against the label's ACTIVE signature:
        one call, ``tokens`` emitted — the per-record flops/token and
        bytes/token denominators. (Notes landing before any stash are
        held and folded into the label's first stash.) ``facts`` are what
        the caller knows of the program as it was traced (the training
        step's ``zero_scatter_leaves``); the record carries the last."""
        stash = self._active.get(label)
        if stash is not None:
            stash.calls += 1
            stash.tokens += tokens
            stash.facts.update(facts)
            return
        p = self._pending.get(label)
        if p is None:
            p = self._pending.setdefault(label, [0, 0])
        p[0] += 1
        p[1] += tokens

    def observe_step(self, label, dispatch_s, wait_s):
        """One step's split, from the caller's own clock readings around
        work it does anyway: ``dispatch_s`` the jitted call (host
        schedule), ``wait_s`` the harvest that blocks on its outputs
        (device wait). Costs no sync. The total feeds the per-program
        EWMA the roofline gauges divide by."""
        step_s = dispatch_s + wait_s
        prev = self._step_s.get(label)
        self._step_s[label] = (step_s if prev is None
                               else 0.8 * prev + 0.2 * step_s)
        d = self._decomp.setdefault(label, [0, 0.0, 0.0])
        d[0] += 1
        d[1] += dispatch_s
        d[2] += wait_s
        if self._registry is not None:
            self._registry.histogram(
                "xray_host_dispatch_seconds",
                program=label).observe(dispatch_s)
            self._registry.histogram(
                "xray_device_wait_seconds",
                program=label).observe(wait_s)
        return step_s

    # ------------------------------------------------------ cold path

    def platform(self):
        if self._platform is None:
            import jax

            self._platform = jax.default_backend()
        return self._platform

    def peaks(self):
        """The roofline peaks row: the ``peaks`` override, else the
        attached device's ``DEVICE_PEAKS`` row. The CPU has no row and
        gets None — no utilization is ever derived there; an accelerator
        whose kind is not in the table raises."""
        if self._peaks_override is not None:
            return self._peaks_override
        if self.platform() == "cpu":
            return None
        import jax

        return device_peaks(jax.devices()[0].device_kind)

    def _analyze(self, stash):
        """AOT lower+compile the stashed program and read the compiler
        out: fingerprint, cost_analysis, memory_analysis. Cached per
        (program, signature); never touches the jit wrapper's dispatch
        cache, so this cannot register as a recompile."""
        key = (id(stash.jitted), stash.sig)
        hit = self._analysis.get(key)
        if hit is not None:
            return hit
        out = {"fingerprint": None, "flops": 0.0, "bytes_accessed": 0.0,
               "argument_bytes": 0, "output_bytes": 0, "temp_bytes": 0,
               "alias_bytes": 0, "generated_code_bytes": 0,
               "peak_hbm_bytes": 0, "kernel_calls": 0, "collectives": {},
               "error": None}
        try:
            lowered = stash.jitted.lower(*stash.args, **stash.kwargs)
            out["fingerprint"] = hashlib.sha256(
                lowered.as_text().encode()).hexdigest()[:16]
            compiled = lowered.compile()
            text = compiled.as_text()
            _record_op_names(text)
            # What the compiler actually put in the program: Pallas
            # kernels (a kernel that ran in interpret mode, or gave way
            # to a jnp reference, leaves no custom call) and the
            # collectives the partitioner inserted.
            out["kernel_calls"] = text.count(_KERNEL_CALL)
            for op in _COLLECTIVE_OPS:
                n = text.count(" {}(".format(op)) + \
                    text.count(" {}-start(".format(op))
                if n:
                    out["collectives"][op] = n
            cost = compiled.cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else {}
            out["flops"] = float(cost.get("flops", 0.0) or 0.0)
            out["bytes_accessed"] = float(
                cost.get("bytes accessed", 0.0) or 0.0)
            mem = compiled.memory_analysis()
            if mem is not None:
                arg = int(getattr(mem, "argument_size_in_bytes", 0) or 0)
                o = int(getattr(mem, "output_size_in_bytes", 0) or 0)
                tmp = int(getattr(mem, "temp_size_in_bytes", 0) or 0)
                ali = int(getattr(mem, "alias_size_in_bytes", 0) or 0)
                out.update({
                    "argument_bytes": arg, "output_bytes": o,
                    "temp_bytes": tmp, "alias_bytes": ali,
                    "generated_code_bytes": int(getattr(
                        mem, "generated_code_size_in_bytes", 0) or 0),
                    # Aliased (donated) buffers are counted once: the
                    # output lives in the argument's allocation.
                    "peak_hbm_bytes": max(0, arg + o + tmp - ali),
                })
        except Exception as e:  # pragma: no cover - backend-specific
            out["error"] = "{}: {}".format(type(e).__name__, e)
            logger.warning(
                "telemetry: xray analysis of %r failed (%s); recording "
                "shapes only", stash.label, out["error"])
        self._analysis[key] = out
        return out

    def materialize(self):
        """Compile-and-analyze every stash that hasn't been, publish
        the per-program gauges, and fill pending recompile-event
        fingerprints. Export paths call this; step paths never do."""
        with self._lock:
            pending = [s for chain in self._programs.values()
                       for s in chain if s.record is None]
        for stash in pending:
            analysis = self._analyze(stash)
            stash.record = dict(
                analysis,
                program=stash.label,
                platform=self.platform(),
                input_shapes=_shapes_of(stash.sig),
                donated=list(stash.donate),
            )
        for ev in self.recompile_events:
            for side in ("old", "new"):
                if ev[side + "_fingerprint"] is None:
                    rec = ev["_" + side].record
                    if rec is not None:
                        ev[side + "_fingerprint"] = rec["fingerprint"]
        for label in list(self._programs):
            self._publish(label)

    def _active_record(self, label):
        """The ACTIVE signature's record, falling back to any
        materialized record under the label."""
        stash = self._active.get(label)
        if stash is not None and stash.record is not None:
            return stash.record
        for stash in reversed(self._programs.get(label, [])):
            if stash.record is not None:
                return stash.record
        return None

    def _publish(self, label):
        """Create the per-program gauge family (idempotent). Gauges
        read materialized records via set_fn — a scrape can never
        trigger a compile. MFU/MBU appear ONLY when the platform has a
        peaks row AND a sampled step time exists."""
        if self._registry is None or label in self._gauged:
            return
        if self._active_record(label) is None:
            return
        self._gauged.add(label)
        plat = self.platform()
        reg = self._registry

        def rec_field(field, label=label):
            rec = self._active_record(label)
            return float(rec[field]) if rec else 0.0

        reg.gauge("xray_flops", program=label, platform=plat).set_fn(
            lambda: rec_field("flops"))
        reg.gauge("xray_bytes_accessed", program=label,
                  platform=plat).set_fn(
            lambda: rec_field("bytes_accessed"))
        reg.gauge("xray_peak_hbm_bytes", program=label,
                  platform=plat).set_fn(
            lambda: rec_field("peak_hbm_bytes"))
        peaks = self.peaks()
        if not peaks:
            return

        def mfu(label=label, peaks=peaks):
            s = self._step_s.get(label)
            return (rec_field("flops", label)
                    / (s * peaks["flops_per_s"]) if s else 0.0)

        def mbu(label=label, peaks=peaks):
            s = self._step_s.get(label)
            return (rec_field("bytes_accessed", label)
                    / (s * peaks["hbm_bytes_per_s"]) if s else 0.0)

        def ratio(label=label, peaks=peaks):
            b = rec_field("bytes_accessed", label)
            balance = peaks["flops_per_s"] / peaks["hbm_bytes_per_s"]
            return (rec_field("flops", label) / b) / balance if b else 0.0

        reg.gauge("xray_mfu", program=label, platform=plat).set_fn(mfu)
        reg.gauge("xray_mbu", program=label, platform=plat).set_fn(mbu)
        reg.gauge("xray_roofline_ratio", program=label,
                  platform=plat).set_fn(ratio)

    def observe(self, label, jitted, *args, tokens=0, **kwargs):
        """Stash + materialize + count, returning the record — the
        flops profiler's synchronous mode. Step paths use stash().
        ``tokens`` is a reserved keyword-only option (see stash())."""
        self.stash(label, jitted, *args, **kwargs)
        self.materialize()
        self.note(label, tokens)
        return self._active_record(label)

    def identity(self, label):
        """One-line program identity for RecompileDetector warnings:
        fingerprint + shapes, old -> new when the signature changed.
        Never compiles — an unmaterialized fingerprint says 'pending'
        (the autopsy's recompile_dicts() resolves it)."""
        cur = self._active.get(label)
        if cur is None:
            return None

        def fp(stash):
            return (stash.record or {}).get("fingerprint") or "pending"

        cur_s = "fingerprint {} shapes ({})".format(
            fp(cur), ", ".join(_shapes_of(cur.sig)))
        old = self._prev_active.get(label)
        if old is None:
            return cur_s
        return "fingerprint {} shapes ({}) -> {}".format(
            fp(old), ", ".join(_shapes_of(old.sig)), cur_s)

    def recompile_dicts(self):
        """Recompile events with fingerprints resolved (materializes)."""
        if self.recompile_events:
            self.materialize()
        return [_public_event(ev) for ev in self.recompile_events]

    def program_count(self):
        """Number of stashed program labels — the cheap fact a
        telemetry snapshot reports (takes the registry lock; never
        materializes)."""
        with self._lock:
            return len(self._programs)

    def max_temp_bytes(self):
        """Largest temp allocation across MATERIALIZED programs (0
        before the first export) — the HBM ledger's program_temp
        component; reading it must never compile."""
        best = 0
        for chain in self._programs.values():
            for stash in chain:
                if stash.record is not None:
                    best = max(best, stash.record["temp_bytes"])
        return best

    def to_json(self):
        """The schema-versioned ``perf_xray`` artifact section."""
        self.materialize()
        programs = []
        flops_total = bytes_total = 0.0
        tokens_total = calls_total = 0
        for label in sorted(self._programs):
            chain = self._programs[label]
            active = self._active.get(label)
            for stash in chain:
                entry = dict(stash.record or {
                    "program": label,
                    "input_shapes": _shapes_of(stash.sig),
                })
                entry["superseded"] = stash is not active
                entry["calls"] = stash.calls
                entry["tokens"] = stash.tokens
                entry.update(stash.facts)
                if stash is active:
                    entry["sampled_step_seconds"] = self._step_s.get(
                        label)
                programs.append(entry)
                # Totals attribute each record's cost to ITS OWN call
                # count (a never-dispatched AOT capture still counts
                # once) — a label cycling through several signatures
                # never bills one signature's cost to another's calls.
                if stash.record is not None:
                    flops_total += (stash.record["flops"]
                                    * max(stash.calls, 1))
                    bytes_total += (stash.record["bytes_accessed"]
                                    * max(stash.calls, 1))
                tokens_total += stash.tokens
                calls_total += stash.calls
        peaks = self.peaks()
        out = {
            "schema_version": SCHEMA_VERSION,
            "platform": self.platform(),
            "peaks": dict(peaks) if peaks else None,
            "programs": programs,
            "totals": {
                "calls": calls_total,
                "tokens": tokens_total,
                "flops_total": flops_total,
                "bytes_total": bytes_total,
                "flops_per_token": (flops_total / tokens_total
                                    if tokens_total else None),
                "bytes_per_token": (bytes_total / tokens_total
                                    if tokens_total else None),
            },
            "recompiles": [_public_event(ev)
                           for ev in self.recompile_events],
            "recompiles_dropped": self.recompile_events_dropped,
            "decomposition": {
                label: {"samples": d[0], "host_dispatch_s": d[1],
                        "device_wait_s": d[2]}
                for label, d in sorted(self._decomp.items())
            },
        }
        return out


class HBMLedger(object):
    """Predicted-vs-live HBM accounting. Components (params, KV arena,
    program temp) are ints or zero-arg callables summed at read time;
    live truth comes from ``device.memory_stats()`` where the backend
    provides it (CPU returns None — the ledger then only predicts).
    Publishes ``hbm_predicted_bytes`` and ``hbm_pressure`` always;
    ``hbm_live_bytes`` / ``hbm_headroom_bytes`` only when the backend
    or a configured capacity makes them meaningful — a gauge that can
    only ever read a made-up number is not published."""

    def __init__(self, registry=None, capacity_bytes=None):
        self._components = {}
        self._capacity = capacity_bytes
        self._registry = registry
        self._gauged = False

    def set_component(self, name, bytes_or_fn):
        self._components[name] = bytes_or_fn
        self._ensure_gauges()

    def _read(self, v):
        return int(v() if callable(v) else v)

    def components(self):
        return {k: self._read(v) for k, v in self._components.items()}

    def predicted(self):
        return sum(self.components().values())

    def live(self):
        """Sum of ``bytes_in_use`` across local devices, or None when
        the backend has no memory_stats (CPU)."""
        try:
            import jax

            total, seen = 0, False
            for d in jax.local_devices():
                stats = d.memory_stats()
                if stats and "bytes_in_use" in stats:
                    total += int(stats["bytes_in_use"])
                    seen = True
            return total if seen else None
        except Exception:
            return None

    def capacity(self):
        """Configured budget, else the device's own ``bytes_limit``,
        else None (unknown)."""
        if self._capacity:
            return int(self._capacity)
        try:
            import jax

            total, seen = 0, False
            for d in jax.local_devices():
                stats = d.memory_stats()
                if stats and "bytes_limit" in stats:
                    total += int(stats["bytes_limit"])
                    seen = True
            return total if seen else None
        except Exception:
            return None

    def headroom(self):
        cap = self.capacity()
        if cap is None:
            return None
        return cap - max(self.live() or 0, self.predicted())

    def pressure(self):
        """0..1 fill fraction (0 when capacity is unknown — the alert
        rule on this gauge can then never fire, by design)."""
        cap = self.capacity()
        if not cap:
            return 0.0
        return max(self.live() or 0, self.predicted()) / cap

    def _ensure_gauges(self):
        if self._registry is None or self._gauged:
            return
        self._gauged = True
        self._registry.gauge("hbm_predicted_bytes").set_fn(
            lambda: float(self.predicted()))
        self._registry.gauge("hbm_pressure").set_fn(self.pressure)
        if self.live() is not None:
            self._registry.gauge("hbm_live_bytes").set_fn(
                lambda: float(self.live() or 0))
        if self.capacity() is not None:
            self._registry.gauge("hbm_headroom_bytes").set_fn(
                lambda: float(self.headroom() or 0))

    def to_json(self):
        return {
            "components": self.components(),
            "predicted_bytes": self.predicted(),
            "live_bytes": self.live(),
            "capacity_bytes": self.capacity(),
            "headroom_bytes": self.headroom(),
            "pressure": round(self.pressure(), 6),
        }


# --------------------------------------------------------- report gate

_GATE_METRICS = ("flops", "bytes_accessed", "peak_hbm_bytes")


def _active_by_label(section):
    out = {}
    for entry in section.get("programs", ()):
        if not entry.get("superseded"):
            out[entry.get("program")] = entry
    return out


def cost_model_gate(baseline, candidate, rel_tol=0.25):
    """Compare two ``perf_xray`` sections program-by-program. These are
    COMPILE-TIME facts — deterministic per (program, shapes, backend) —
    so the tolerance is for intentional small drift, not noise: A/A is
    identical by construction. An increase beyond ``rel_tol`` in flops,
    bytes accessed, or peak HBM (per program, or per token at the
    totals level) flags; decreases land in ``improved``. Platform or
    schema mismatches caveat instead of comparing apples to oranges."""
    out = {"pass": True, "flagged": [], "improved": [], "caveats": [],
           "programs": {}, "totals": {}}
    if not baseline or not candidate:
        out["caveats"].append("perf_xray missing on one side; "
                              "nothing compared")
        return out
    if baseline.get("schema_version") != candidate.get("schema_version"):
        out["caveats"].append(
            "schema_version mismatch ({} vs {}); nothing compared"
            .format(baseline.get("schema_version"),
                    candidate.get("schema_version")))
        return out
    if baseline.get("platform") != candidate.get("platform"):
        out["caveats"].append(
            "platform mismatch ({} vs {}): cost-model deltas may "
            "reflect backend lowering, not code".format(
                baseline.get("platform"), candidate.get("platform")))
    base_p = _active_by_label(baseline)
    cand_p = _active_by_label(candidate)
    for label in sorted(set(base_p) | set(cand_p)):
        if label not in base_p or label not in cand_p:
            out["caveats"].append(
                "program {!r} only in {}".format(
                    label,
                    "baseline" if label in base_p else "candidate"))
            continue
        b, c = base_p[label], cand_p[label]
        row = {}
        for metric in _GATE_METRICS:
            bv = float(b.get(metric) or 0.0)
            cv = float(c.get(metric) or 0.0)
            rel = (cv - bv) / bv if bv else (1.0 if cv else 0.0)
            row[metric] = {"baseline": bv, "candidate": cv,
                           "rel_delta": round(rel, 6)}
            if rel > rel_tol:
                out["flagged"].append(
                    "{}.{}: {:+.1%} ({:.3g} -> {:.3g})".format(
                        label, metric, rel, bv, cv))
                out["pass"] = False
            elif rel < -rel_tol:
                out["improved"].append(
                    "{}.{}: {:+.1%}".format(label, metric, rel))
        if (b.get("fingerprint") and c.get("fingerprint")
                and b["fingerprint"] != c["fingerprint"]):
            row["fingerprint_changed"] = True
        out["programs"][label] = row
    for metric in ("flops_per_token", "bytes_per_token"):
        bv = (baseline.get("totals") or {}).get(metric)
        cv = (candidate.get("totals") or {}).get(metric)
        if bv is None or cv is None:
            continue
        rel = (cv - bv) / bv if bv else (1.0 if cv else 0.0)
        out["totals"][metric] = {"baseline": bv, "candidate": cv,
                                 "rel_delta": round(rel, 6)}
        if rel > rel_tol:
            out["flagged"].append(
                "totals.{}: {:+.1%} ({:.3g} -> {:.3g})".format(
                    metric, rel, bv, cv))
            out["pass"] = False
        elif rel < -rel_tol:
            out["improved"].append(
                "totals.{}: {:+.1%}".format(metric, rel))
    return out


# ---------------------------------------------------------- self-check

def _self_check():
    """``python -m deepspeed_tpu.telemetry.xray --self-check``: peak
    table sanity, determinism of the fingerprint/cost pipeline on a
    tiny real program, schema shape, and gate A/A + synthetic-delta
    behavior. Exit 0 on success (bin/lint.sh runs this)."""
    failures = []
    for kind, row in DEVICE_PEAKS.items():
        if not (row.get("flops_per_s", 0) > 0
                and row.get("hbm_bytes_per_s", 0) > 0
                and row.get("source")):
            failures.append("peaks[{}] not positive or unsourced: {}"
                            .format(kind, row))
    try:
        import jax
        import jax.numpy as jnp

        fn = jax.jit(lambda a, b: jnp.tanh(a @ b).sum())
        x = jnp.ones((8, 16), jnp.float32)
        y = jnp.ones((16, 4), jnp.float32)
        r1 = ProgramRegistry().observe("probe", fn, x, y, tokens=1)
        r2 = ProgramRegistry().observe("probe", fn, x, y, tokens=1)
        if r1["fingerprint"] is None or \
                r1["fingerprint"] != r2["fingerprint"]:
            failures.append("fingerprint not deterministic: {} vs {}"
                            .format(r1["fingerprint"], r2["fingerprint"]))
        if r1["flops"] <= 0 or r1["flops"] != r2["flops"]:
            failures.append("cost_analysis flops not deterministic/"
                            "positive: {} vs {}".format(
                                r1["flops"], r2["flops"]))
        xr = ProgramRegistry()
        xr.observe("probe", fn, x, y, tokens=4)
        section = xr.to_json()
        for key in ("schema_version", "platform", "programs", "totals",
                    "recompiles", "decomposition"):
            if key not in section:
                failures.append("perf_xray section missing {!r}"
                                .format(key))
        if section["schema_version"] != SCHEMA_VERSION:
            failures.append("schema_version drift")
        aa = cost_model_gate(section, section)
        if not aa["pass"] or aa["flagged"]:
            failures.append("A/A gate did not pass clean: {}".format(aa))
        import copy

        doubled = copy.deepcopy(section)
        for entry in doubled["programs"]:
            entry["bytes_accessed"] *= 2
        doubled["totals"]["bytes_per_token"] = (
            section["totals"]["bytes_per_token"] * 2)
        ab = cost_model_gate(section, doubled)
        if ab["pass"] or not any("bytes" in f for f in ab["flagged"]):
            failures.append(
                "2x bytes delta not flagged: {}".format(ab))
        ledger = HBMLedger(capacity_bytes=1000)
        ledger.set_component("a", 600)
        ledger.set_component("b", lambda: 100)
        if ledger.predicted() != 700 or ledger.headroom() != 300 \
                or abs(ledger.pressure() - 0.7) > 1e-9:
            failures.append("ledger arithmetic wrong: {}".format(
                ledger.to_json()))
    except Exception as e:  # pragma: no cover - env without jax
        failures.append("self-check probe failed: {}: {}".format(
            type(e).__name__, e))
    if failures:
        for f in failures:
            print("xray self-check FAIL: {}".format(f))
        return 1
    print("xray self-check OK: peaks table sane, fingerprints/cost "
          "deterministic, schema v{}, gate A/A clean + 2x delta flagged"
          .format(SCHEMA_VERSION))
    return 0


if __name__ == "__main__":  # pragma: no cover
    import sys

    sys.exit(_self_check())
