"""Zero-cost source annotations for the graftlint static analyzer.

This module is imported by HOT code (models/generation.py, the serving
engine, the decode kernels), so it must stay dependency-free and the
markers must cost nothing at runtime:

- ``hot_path`` is an IDENTITY decorator: it returns the function object
  unchanged (no wrapper frame, no functools.wraps, nothing for jax.jit
  or pickle to trip over) after stamping ``__graftlint_hot_path__`` on
  it. The analyzer reads the DECORATOR SYNTAX from the AST — the stamp
  exists only so runtime introspection agrees with the source.
- ``_THREAD_OWNED`` is a plain class attribute (a frozenset of attribute
  names) that classes checked by the THREADRACE rule declare; see
  docs/ANALYSIS.md. There is nothing to import for it — the convention
  is documented here because this module is the annotations registry.

The allowlists below are the analyzer's second source of truth: the
functions named here are hot-path (HOSTSYNC/DETERMINISM apply to their
whole body, nested defs included) even if someone deletes the decorator,
and the sanctioned-sync sites are the ONLY places allowed to pay a
device->host transfer via the kv_pool harvest helpers.
"""


def hot_path(fn):
    """Mark ``fn`` as serving/decode hot-path code: no implicit
    device->host syncs (HOSTSYNC) and no wall-clock/unseeded RNG
    (DETERMINISM) anywhere in its body. Identity decorator — returns
    ``fn`` itself, so ``hot_path(f) is f`` and jit/pickle/vmap see the
    undecorated function."""
    fn.__graftlint_hot_path__ = True
    return fn


# Functions that are hot-path by decree, keyed by canonical module path
# (path from the repo root). The @hot_path decorator in the source is
# the primary marker; this list is the analyzer's backstop so removing
# a decorator cannot silently unprotect a hot path. Names match the
# LAST segment of the function's qualname.
HOT_PATH_FUNCTIONS = {
    "deepspeed_tpu/inference/engine.py": frozenset({
        "_mixed_step_program", "_decode_chunk_program",
        "_spec_decode_chunk_program", "_sample_rows",
    }),
    "deepspeed_tpu/models/generation.py": frozenset({
        "_forward", "decode_step", "append_forward", "verify_forward",
        "ngram_draft", "accept_counts",
    }),
    "deepspeed_tpu/inference/kv_pool.py": frozenset({
        "cache_view", "slot_cache_view", "write_slot_cache", "fold_cache",
    }),
    "deepspeed_tpu/ops/transformer/kernels/decode_attention.py": frozenset({
        "flash_decode_attention", "flash_decode_attention_q8",
        "quantize_kv", "dequantize_kv", "decode_attention_reference",
        "decode_attention_q8_reference",
        "flash_decode_attention_paged", "flash_decode_attention_paged_q8",
        "decode_attention_paged_reference",
        "decode_attention_paged_q8_reference", "kv_append",
    }),
}

# The only functions allowed to call the kv_pool sync helpers in their
# own-sync form (``harvest_snapshot``, or ``max_active_frontier`` /
# ``free_slots`` WITHOUT ``snap=``): the documented once-per-step
# snapshot points (engine step boundaries) and the helpers themselves
# (kv_pool's snap=None fallback is the documented opt-in). Everywhere
# else must pass ``snap=`` and reuse an already-paid transfer.
SANCTIONED_SYNC_SITES = {
    "deepspeed_tpu/inference/kv_pool.py": frozenset({
        "harvest_snapshot", "max_active_frontier", "free_slots",
    }),
    "deepspeed_tpu/inference/engine.py": frozenset({
        "_harvest_step",
    }),
}

# Modules where DETERMINISM applies to EVERY function, not just
# hot-path-annotated ones: seeded-workload generation (a WorkloadSpec
# must replay bit-identically from its seed) and the decode program
# source (traced code must never read ambient entropy).
DETERMINISM_MODULES = (
    "deepspeed_tpu/loadgen/workload.py",
    "deepspeed_tpu/models/generation.py",
    "deepspeed_tpu/inference/kv_pool.py",
)

# Classes the THREADRACE rule always checks, manifest or not (a class
# that also DEFINES ``_THREAD_OWNED`` opts in wherever it lives).
THREAD_CHECKED_CLASSES = ("InferenceEngine", "ServingFleet",
                          "PrefixDirectory", "HandoffPump",
                          "FrontDoor", "TokenStream",
                          "AlertManager", "TraceContext")
