"""InferenceConfig — the serving engine's knob surface.

Mirrors the runtime side's declarative config style (runtime/config.py):
one dataclass, one ``from_dict`` that rejects unknown keys (a typo like
``"max_slot"`` must not silently serve with defaults), and validation
against the model's position budget at engine construction.

Every field is a COMPILE-SHAPE knob or a host-side policy knob — nothing
here varies per request (per-request sampling params travel as traced
device values, see engine.py), which is what bounds the compile count:
ONE mixed-step program serves prefill chunks and decode together.
"""

import dataclasses
import os
from typing import Optional

# The JSON block under "inference" in ds_config (runtime/config.py reads
# it with these defaults; InferenceConfig.from_dict consumes the result).
INFERENCE_DEFAULTS = {
    "max_slots": 8,
    "max_len": 512,
    "chunk_size": 16,
    "max_queue": 64,
    "eos_token_id": None,
    "max_new_tokens": 128,
    "use_flash_decode": None,
    "prefill_chunk": 32,
    "spec_decode": None,
    "spec_k": 4,
    "spec_ngram": 3,
    "telemetry": True,
    "trace_ring": 4096,
    "perf_xray": True,
    "fault_injection": False,
    "step_budget_s": None,
    "recovery_max_retries": 2,
    "recovery_backoff_s": 0.0,
    "replica_id": None,
    "int8_kv": False,
    "prefix_cache": False,
    "prefix_slots": 8,
    "prefix_len": 64,
    "min_prefix_len": 8,
    "host_offload": False,
    "swap_slots": 8,
    "hbm_budget_bytes": None,
    "role": "mixed",
    "paged_kv": False,
    "kv_page_len": 128,
    "kv_pages": None,
    "denoising_steps": None,
}


@dataclasses.dataclass(frozen=True)
class InferenceConfig:
    # Fixed number of concurrently-decoding sequences: the batch dim of
    # the KV pool. Batch composition changes by slot assignment, never by
    # reshaping, so the decode program compiles exactly once.
    max_slots: int = 8
    # KV-cache length per slot; prompt_len + max_new_tokens must fit.
    max_len: int = 512
    # Tokens decoded per jitted chunk (one lax.scan trip count). Admission
    # and eviction happen only at chunk boundaries: larger chunks amortize
    # dispatch, smaller chunks cut admission latency.
    chunk_size: int = 16
    # Queued (not yet admitted) request cap — submit() raises QueueFull
    # beyond it. The backpressure boundary for upstream callers.
    max_queue: int = 64
    # Default EOS id for requests that don't specify one (None: no EOS,
    # sequences run to max_new_tokens).
    eos_token_id: Optional[int] = None
    # Default per-request new-token budget.
    max_new_tokens: int = 128
    # Decode-attention kernel selection: True forces the Pallas
    # flash-decode kernel (ops/transformer/kernels/decode_attention.py),
    # False forces the dense einsum path, None defers to the model config
    # and then generation.default_flash_decode() (on by default on TPU).
    # When the kernel is on, the KV pool pads max_len up to the kernel's
    # 128-position block quantum (admission limits still enforce the
    # configured max_len).
    use_flash_decode: Optional[bool] = None
    # Chunked prefill (Sarathi-style): prompts are consumed
    # ``prefill_chunk`` tokens at a time INSIDE the decode step program —
    # one mixed-batch program total, no per-bucket prefill compiles, no
    # decode stall while a long prompt admits. ``prefill_chunk`` is the
    # prompt tokens consumed per engine step while a slot is prefilling.
    # Larger chunks finish prefill in fewer steps (better TTFT for the
    # prefilling request); smaller chunks bound the extra latency each
    # step adds for already-decoding slots. Also the KV plane slack the
    # pool over-allocates so frontier writes never clamp.
    prefill_chunk: int = 32
    # Speculative decoding (n-gram self-drafting + multi-token verify,
    # fused into the mixed-step program — engine.py docstring): True
    # enables it engine-wide, False disables, None defers to the
    # DS_TPU_SPEC_DECODE env and then to OFF (opt-in: acceptance depends
    # on workload repetitiveness, and the verify pass widens every decode
    # step from 1 to spec_k+1 query rows). Per-request opt-out via
    # submit(spec_decode=False) cohabits the same program.
    spec_decode: Optional[bool] = None
    # Draft length K: each decode step verifies K drafted tokens plus the
    # frontier token in one K+1-row forward, emitting 1..K+1 tokens.
    # Larger K wins more on repetitive output but pays a wider verify
    # whether or not the draft survives.
    spec_k: int = 4
    # N-gram length the drafter matches against the slot's own context.
    # Longer n-grams fire less often but predict better when they do.
    spec_ngram: int = 3
    # Telemetry (telemetry/): per-request trace spans, profiler
    # annotations, and recompile observation. False swaps in the
    # NullRecorder and skips annotation scopes — the metrics REGISTRY
    # stays on either way (counters are the engine's own bookkeeping and
    # cost one float add each), so ``metrics()`` is always correct.
    telemetry: bool = True
    # Flight-recorder ring capacity (events, not bytes): the newest
    # trace_ring span/instant events are retained for export; exact
    # per-name span COUNTS survive wraparound.
    trace_ring: int = 4096
    # Perf X-ray (telemetry/xray.py): the compiled-program cost/memory
    # observatory. On (the default), every program call site stashes
    # its shape signature (tens of microseconds, no device touch) and
    # export paths — perf_xray(), a traced benchmark run — pay the one-time
    # AOT lower+compile that reads XLA's cost/memory model. Off, no
    # stash, no ledger, no roofline gauges.
    perf_xray: bool = True
    # Chaos switch: engine.inject_faults(FaultPlan) only arms when True
    # (inference/faults.py). Off (the default), the injector is None and
    # every hook is one ``is not None`` test — production configs cannot
    # be chaos'd by accident. docs/RESILIENCE.md is the fault model.
    fault_injection: bool = False
    # Step watchdog wall-clock budget (seconds): a step still running
    # past it trips the watchdog — warning log + ``step_stalls`` counter
    # + degraded health — instead of the run going silently quiet. None
    # (the default) disables the watchdog; detection only, a wedged
    # device call cannot be preempted host-side (resilience.py).
    step_budget_s: Optional[float] = None
    # CONSECUTIVE failed-step recoveries tolerated before the engine
    # transitions to dead (terminal; step()/submit() raise
    # EngineDeadError). A clean step resets the streak — transient
    # faults retry forever, a persistently failing device does not.
    recovery_max_retries: int = 2
    # Sleep before the Nth consecutive recovery attempt: backoff_s * N
    # (linear). 0 disables — tests and single-fault chaos runs recover
    # immediately.
    recovery_backoff_s: float = 0.0
    # Identity within a ServingFleet (inference/fleet.py): stamped into
    # telemetry const labels, QueueFull payloads, and log lines so every
    # signal a router consumes is attributable. None for a standalone
    # engine — no labels, identical output to pre-fleet builds.
    replica_id: Optional[int] = None
    # --- KV memory hierarchy (inference/kv_hierarchy/) ------------------
    # Store the KV pool as int8 codes with fp32 per-(head, position)
    # scales; the flash-decode kernel dequantizes in-block (the
    # "decode_attention_q8" family) and the einsum path dequantizes
    # before attending. Roughly quarters the plane bytes per slot at the
    # cost of <= scale/2 per-element reconstruction error.
    int8_kv: bool = False
    # Shared-prefix cache: a host-side radix trie over prompt token ids
    # detects shared prefixes at admission and aliases the matched span
    # onto a read-only prefix plane — the slot's private plane only holds
    # the suffix, and prefill skips the aliased span entirely (the TTFT
    # win).
    prefix_cache: bool = False
    # Read-only prefix plane rows (compile-shape: the gather dimension of
    # the prefix store). Refcounted; LRU-evicted when full.
    prefix_slots: int = 8
    # Max positions a prefix row holds — longer shared spans alias only
    # their first prefix_len positions.
    prefix_len: int = 64
    # Shortest shared span worth aliasing: matches below this prefill
    # normally (trie bookkeeping overhead would exceed the saving).
    min_prefix_len: int = 8
    # Host offload: swap an idle session's KV slot (planes + scalars) to
    # host RAM via fixed-shape transfers and restore on resume, driven by
    # the scheduler's ``swapped`` phase.
    host_offload: bool = False
    # Max concurrently swapped-out sessions (bounds host RAM at
    # swap_slots * bytes-per-slot).
    swap_slots: int = 8
    # Simulated HBM budget for the effective_slots capacity gauge
    # (telemetry): how many slots WOULD fit in this many bytes under the
    # current hierarchy config. None: use the flat-fp pool's own
    # footprint as the budget, making the gauge a direct "x more slots
    # at the bytes we used to spend" ratio.
    hbm_budget_bytes: Optional[int] = None
    # --- Disaggregated prefill/decode serving (inference/fleet.py) ------
    # Phase role within a ServingFleet. "mixed" (the default) serves
    # both phases — a standalone engine or a classic fleet replica.
    # "prefill" runs prompts only: once a request's final chunk lands,
    # the engine parks it in the ``handoff`` phase and snapshots its KV
    # slot to a host record for the fleet's handoff pump to migrate.
    # "decode" advertises that this replica accepts those migrations and
    # should not be routed new prompts (routing honors it; the engine
    # itself stays fully capable of prefill — failover re-prefill on a
    # decode replica is the fallback that keeps zero-lost true). Both
    # non-mixed roles ride the mixed-step program (the prefill lane is
    # lax.cond-skipped when unused), so compile_count stays 1 either
    # way.
    role: str = "mixed"
    # --- Paged KV cache (inference/paging.py + kv_pool.py) --------------
    # Store the KV plane as a shared PAGE ARENA [L, P, H/g, page_len, g*D]
    # (g heads a 128-lane tile, from the head dim: inference/kv_pool.py)
    # plus a per-slot int32 block table [slots, plane_len/page_len]:
    # pages are allocated on demand as frontiers advance and freed at
    # release, so a slot only ever holds HBM proportional to its actual
    # length (vLLM-style paged attention under XLA static shapes — the
    # arena and table SHAPES are fixed, only the table VALUES change,
    # so the compiled step program never recompiles). Admission becomes
    # page-aware: each request reserves ceil((prompt + max_new + slack)
    # / page_len) pages up front, which is what turns the heavy-tailed
    # length mix into a >= 3x concurrent-session win at fixed HBM.
    # False (the default) keeps the dense slotted pool — the A/B arm
    # and the training-side baseline.
    paged_kv: bool = False
    # Page length in positions — the block-table granularity AND the
    # flash-decode block quantum (kernel blocks == pages; the Pallas
    # paged kernel engages when this is a multiple of its 128-position
    # BLOCK_MIN, the einsum gather path serves any value — small pages
    # keep CPU tests cheap).
    kv_page_len: int = 128
    # Total pages in the arena (the HBM budget in page units). None
    # derives capacity parity with the dense pool: max_slots *
    # (plane_len / page_len) pages, i.e. the same bytes — set it lower
    # to pin HBM and let page-aware admission carry more sessions.
    kv_pages: Optional[int] = None
    # --- Generation by diffusion over blocks ----------------------------
    # Read only for a model whose adapter says ``block_length`` > 1
    # (docs/INFERENCE.md): the denoising passes a block gets before its
    # commit pass, a request's quality knob (``submit(denoising_steps=)``
    # overrides it). It must divide the block length: a pass unmasks
    # ``block_length / denoising_steps`` positions. None: the block length
    # itself, one token a pass.
    denoising_steps: Optional[int] = None

    def __post_init__(self):
        if self.max_slots < 1:
            raise ValueError("inference.max_slots must be >= 1, got "
                             "{}".format(self.max_slots))
        if self.chunk_size < 1:
            raise ValueError("inference.chunk_size must be >= 1, got "
                             "{}".format(self.chunk_size))
        if self.max_queue < 1:
            raise ValueError("inference.max_queue must be >= 1, got "
                             "{}".format(self.max_queue))
        if self.prefill_chunk < 1:
            raise ValueError("inference.prefill_chunk must be >= 1, got "
                             "{}".format(self.prefill_chunk))
        if self.spec_k < 1:
            raise ValueError("inference.spec_k must be >= 1, got "
                             "{}".format(self.spec_k))
        if self.spec_ngram < 1:
            raise ValueError("inference.spec_ngram must be >= 1, got "
                             "{}".format(self.spec_ngram))
        if self.trace_ring < 1:
            raise ValueError("inference.trace_ring must be >= 1, got "
                             "{}".format(self.trace_ring))
        if self.step_budget_s is not None and self.step_budget_s <= 0:
            raise ValueError("inference.step_budget_s must be > 0 (or None "
                             "to disable the watchdog), got "
                             "{}".format(self.step_budget_s))
        if self.recovery_max_retries < 0:
            raise ValueError("inference.recovery_max_retries must be >= 0, "
                             "got {}".format(self.recovery_max_retries))
        if self.recovery_backoff_s < 0:
            raise ValueError("inference.recovery_backoff_s must be >= 0, "
                             "got {}".format(self.recovery_backoff_s))
        if self.replica_id is not None and self.replica_id < 0:
            raise ValueError("inference.replica_id must be >= 0 (or None "
                             "outside a fleet), got "
                             "{}".format(self.replica_id))
        if self.prefix_slots < 1:
            raise ValueError("inference.prefix_slots must be >= 1, got "
                             "{}".format(self.prefix_slots))
        if self.min_prefix_len < 1:
            raise ValueError("inference.min_prefix_len must be >= 1, got "
                             "{}".format(self.min_prefix_len))
        if self.prefix_len < self.min_prefix_len:
            raise ValueError(
                "inference.prefix_len={} must be >= min_prefix_len={}"
                .format(self.prefix_len, self.min_prefix_len))
        if self.prefix_len > self.max_len:
            raise ValueError(
                "inference.prefix_len={} exceeds max_len={}".format(
                    self.prefix_len, self.max_len))
        if self.swap_slots < 1:
            raise ValueError("inference.swap_slots must be >= 1, got "
                             "{}".format(self.swap_slots))
        if self.role not in ("mixed", "prefill", "decode"):
            raise ValueError(
                "inference.role must be one of 'mixed'/'prefill'/'decode', "
                "got {!r}".format(self.role))
        if self.kv_page_len < 1:
            raise ValueError("inference.kv_page_len must be >= 1, got "
                             "{}".format(self.kv_page_len))
        if self.kv_pages is not None and self.kv_pages < 1:
            raise ValueError("inference.kv_pages must be >= 1 (or None for "
                             "dense-parity capacity), got "
                             "{}".format(self.kv_pages))
        if self.hbm_budget_bytes is not None and self.hbm_budget_bytes <= 0:
            raise ValueError(
                "inference.hbm_budget_bytes must be > 0 (or None for the "
                "flat-pool baseline), got {}".format(self.hbm_budget_bytes))

    @classmethod
    def from_dict(cls, block):
        """Build from a ds_config ``inference`` block (or any dict with the
        same keys). Unknown keys raise — the block is the public config
        contract and typos must be loud."""
        block = dict(block or {})
        unknown = set(block) - set(INFERENCE_DEFAULTS)
        if unknown:
            raise ValueError(
                "unknown inference config key(s) {}; valid keys: {}".format(
                    sorted(unknown), sorted(INFERENCE_DEFAULTS)))
        return cls(**dict(INFERENCE_DEFAULTS, **block))

    def resolved_spec_decode(self):
        """The effective speculative-decoding switch: the explicit field
        wins; ``None`` defers to the ``DS_TPU_SPEC_DECODE`` env (any
        value but ``0``/``false`` turns it on; only tests set it today);
        the final default is off."""
        if self.spec_decode is not None:
            return bool(self.spec_decode)
        env = os.environ.get("DS_TPU_SPEC_DECODE", "")
        if env:
            return env not in ("0", "false")
        return False

    def validate_against_model(self, n_positions):
        if self.max_len > n_positions:
            raise ValueError(
                "inference.max_len={} exceeds the model's n_positions={}"
                .format(self.max_len, n_positions))
