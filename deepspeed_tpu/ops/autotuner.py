"""Per-shape kernel autotuner — the TPU analog of the reference's GEMM
algorithm sweeps (csrc/includes/gemm_test.h:27,141: GemmTest/StridedGemmTest
try every cublas algo at layer construction and pick the fastest).

On TPU the tunable axis is Pallas tile sizes, not cublas algos. Selection
order per (kernel, shape-signature) key:

1. in-process memo;
2. a bundled offline table shipped with the package (tuned on real
   hardware, keyed by platform);
3. a user cache file (~/.cache/deepspeed_tpu/autotune.json), populated by
   online sweeps;
4. when ``DS_TPU_AUTOTUNE=1``, an online sweep: time every candidate with
   compile excluded (one warmup, then min of ``repeats``), persist the
   winner to the user cache. Otherwise: the caller's default.

Online sweeps cost one kernel compile per candidate, so they are opt-in —
like the reference, which also pays its sweep at layer creation, not
silently per step.
"""

import json
import os
import time

import jax

_MEMO = {}
_BUNDLED = None
_USER = None

_BUNDLED_PATH = os.path.join(os.path.dirname(__file__), "autotune_table.json")


def _user_cache_path():
    base = os.environ.get("XDG_CACHE_HOME",
                          os.path.join(os.path.expanduser("~"), ".cache"))
    return os.path.join(base, "deepspeed_tpu", "autotune.json")


def _load(path):
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _tables():
    global _BUNDLED, _USER
    if _BUNDLED is None:
        _BUNDLED = _load(_BUNDLED_PATH)
    if _USER is None:
        _USER = _load(_user_cache_path())
    return _BUNDLED, _USER


def online_enabled():
    return os.environ.get("DS_TPU_AUTOTUNE", "0") not in ("0", "", "false")


def force_enabled():
    """DS_TPU_AUTOTUNE=force: re-sweep even for shapes already in a table
    (used to refresh stale tables after a kernel redesign changes the
    cost surface). Winners still land in the user cache."""
    return os.environ.get("DS_TPU_AUTOTUNE", "") == "force"


def _sync(out):
    """Execution barrier via a scalar VALUE fetch, so the timing covers
    the kernel and not only its dispatch."""
    leaf = jax.tree_util.tree_leaves(out)[0]
    return float(leaf.ravel()[0].astype("float32"))


def _time_candidate(run, repeats):
    _sync(run())  # compile + warmup
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        _sync(run())
        best = min(best, time.perf_counter() - t0)
    return best


# Hardware tile quantum per kernel family: every block size in a table
# entry must be a positive multiple of its family's minimum (one 128-lane
# row). Families not listed here only get the positive-int check.
_KERNEL_MIN_BLOCK = {
    "flash_attention": 128,
    "decode_attention": 128,
    "decode_attention_q8": 128,
}


def validate_table(table, source="autotune table"):
    """Schema-check a tile table (the bundled file or a user cache dump):
    every key must parse as ``platform::kernel::signature`` with non-empty
    parts, every entry must be a dict with a ``choice`` list of positive
    ints, and kernels with a known tile quantum (_KERNEL_MIN_BLOCK)
    additionally require each block to be a positive multiple of it.
    Raises ValueError naming the offending key; returns the number of
    entries checked. Guards hand-edits from hardware sweeps — a malformed
    entry would otherwise break kernel dispatch at serving time
    (tests/unit/test_autotune_table.py runs this over the bundled file)."""
    if not isinstance(table, dict):
        raise ValueError("{}: expected a JSON object at top level, got "
                         "{}".format(source, type(table).__name__))
    for key, entry in table.items():
        parts = key.split("::")
        if len(parts) != 3 or not all(parts):
            raise ValueError(
                "{}: key {!r} does not parse as "
                "platform::kernel::signature".format(source, key))
        kernel = parts[1]
        if not isinstance(entry, dict) or "choice" not in entry:
            raise ValueError(
                "{}: entry for {!r} must be an object with a 'choice' "
                "list".format(source, key))
        choice = entry["choice"]
        blocks = choice if isinstance(choice, list) else [choice]
        if not blocks:
            raise ValueError(
                "{}: entry for {!r} has an empty choice".format(source, key))
        min_block = _KERNEL_MIN_BLOCK.get(kernel)
        for blk in blocks:
            if isinstance(blk, bool) or not isinstance(blk, int) or blk <= 0:
                raise ValueError(
                    "{}: entry for {!r} has non-positive-int block "
                    "{!r}".format(source, key, blk))
            if min_block and blk % min_block:
                raise ValueError(
                    "{}: entry for {!r} has block {} not a multiple of "
                    "{}'s minimum {}".format(source, key, blk, kernel,
                                             min_block))
    return len(table)


def table_key(kernel, signature):
    """The full table key for (current backend, kernel, signature) —
    the single place the key format lives, so sweep/promotion scripts
    (tests/perf/autotune_sweep.py) cannot drift from it."""
    return "{}::{}::{}".format(jax.default_backend(), kernel, signature)


def autotune(kernel, signature, candidates, make_run, default, repeats=3):
    """Pick the best candidate for (kernel, signature).

    Args:
      kernel: kernel family name, e.g. "flash_attention".
      signature: hashable shape signature, e.g. "b8_h16_t1024_d64_bf16".
      candidates: list of JSON-able candidate configs.
      make_run: candidate -> zero-arg callable executing the kernel once
        (only called during an online sweep).
      default: returned when no table entry exists and online tuning is off.
    Returns: the chosen candidate.
    """
    platform = jax.default_backend()
    key = table_key(kernel, signature)
    if key in _MEMO:
        return _MEMO[key]
    multiproc = jax.process_count() > 1
    bundled, user = _tables()
    # Multi-controller runs consult ONLY the package-bundled table: every
    # host ships the same file, so every host traces the same tiles. The
    # per-host user cache (and per-host sweeps) could diverge across hosts
    # and compile different executables.
    tables = (bundled,) if multiproc else (user, bundled)
    # force mode only bypasses the tables when a sweep can ACTUALLY run
    # here (eager call, runnable candidates, one controller, on-TPU);
    # otherwise — e.g. the engine's traced calls under
    # DS_TPU_AUTOTUNE=force — tuned tiles must still be served.
    can_sweep = (platform == "tpu" and len(candidates) > 1
                 and not multiproc)
    if not (force_enabled() and can_sweep):
        for table in tables:
            if key in table:
                chosen = table[key]["choice"]
                _MEMO[key] = chosen
                return chosen
    if not (online_enabled() and platform == "tpu" and len(candidates) > 1
            and not multiproc):
        if not online_enabled():
            # With tuning off the answer can never change — memoize. With
            # tuning ON but no runnable candidates (traced call), leave the
            # memo empty so a later EAGER call can still run the sweep.
            _MEMO[key] = default
        return default

    results = []
    errors = []
    for cand in candidates:
        try:
            dt = _time_candidate(make_run(cand), repeats)
        except Exception as e:  # candidate may not fit VMEM — skip it
            errors.append(str(e))
            continue
        results.append((dt, cand))
    if not results:
        if errors:
            # The user asked for tuning and got none — say so instead of
            # silently memoizing the default.
            import warnings
            warnings.warn(
                "autotune({}, {}): all {} candidates failed (first error: "
                "{}); using default {}".format(kernel, signature,
                                               len(candidates), errors[0],
                                               default))
        _MEMO[key] = default
        return default
    best_dt, best = min(results, key=lambda r: r[0])
    _MEMO[key] = best
    path = _user_cache_path()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        user = _load(path)
        user[key] = {"choice": best, "seconds": best_dt,
                     "candidates_timed": len(results)}
        tmp = "{}.tmp.{}".format(path, os.getpid())
        with open(tmp, "w") as f:
            json.dump(user, f, indent=1, sort_keys=True)
        os.replace(tmp, path)  # atomic: concurrent writers can't corrupt
        global _USER
        _USER = user
    except OSError:
        pass
    return best
