"""ModelAdapter — the complete engine<->model contract.

The serving engine (inference/engine.py) is model-agnostic: every model
computation it performs — cache allocation, chunked prefill, the decode
step, speculative verify, drafting — goes through exactly this surface.
No other model import is reachable from hot-path engine code; the
graftlint ADAPTER rule (analysis/rules/adapter.py) enforces that
``models.generation`` is imported inside ``inference/`` ONLY by
``adapters/gpt2.py``.

Contract requirements (pinned by tests/unit/test_adapters.py, the
conformance kit every adapter must pass):

- Adapters are IMMUTABLE and HASHABLE: an adapter instance is the static
  argument of every jitted engine program, so equality/hash must reflect
  the full compiled-behavior configuration (frozen dataclasses over
  hashable config tuples). One adapter => one compiled mixed-step program
  per engine (compile_count == 1).
- The cache is a dict of arrays with per-row frontier ``pos`` [B]; k/v
  planes are [layers, B, heads, plane_len, head_dim] so the KV pool,
  hierarchy (int8 / prefix tiers, host offload) and handoff machinery
  compose unchanged. ``layers`` and ``heads`` are what the CACHE holds
  (``cache_spec()``): the layers that hold keys and the heads a token
  stores, which a hybrid or grouped-query model has fewer of than it has
  layers and query heads. GLOBAL extra state (accumulators) MUST use
  ``aux_``-prefixed keys: the pool threads them through every program,
  the hierarchy's capture/restore skips them (they are not per-slot), and
  ``harvest_snapshot`` fetches them for ``observe``.
- A model may also carry a RECURRENT STATE A ROW, with no position axis
  (a state-space layer's state and its convolution's tail). It IS
  per-slot: ``cache_spec().slot_state`` names it (``slot_``-prefixed
  keys, ``(key, shape a row, dtype)``), the pool allocates it slot-major
  and threads it through every program, ``slot_cache_view`` /
  ``write_slot_cache`` carry one slot's slice through the prefill lane,
  and the hierarchy's capture/restore and the handoff ship it with the
  slot's scalars, so a preempted or migrated session resumes its own
  state. The engine hands the model ``cache['n_valid']`` [B] beside it:
  how many leading columns of each row are real, 0 for a row that must
  not move (an idle slot, a slot still in the lane while the scan runs).
- Positions past a row's frontier may hold garbage that is masked or
  overwritten before the frontier reaches them (the stale-cache rule) —
  this is what makes speculative rollback "don't advance pos" and chunked
  prefill's pad columns free. THE RULE HOLDS FOR KEY/VALUE PLANES ONLY.
  A recurrent state has no frontier to hide garbage behind, so for a
  model that carries one: a pad column and a row that is not decoding
  must leave the state untouched (the model masks by ``n_valid``; an
  idle slot may still decode garbage LOGITS, never garbage state); a row
  whose frontier is 0 starts from a zero state, whatever its slot held
  (slot reuse needs no reset from the host); and rollback is NOT "do not
  advance pos", so what rests on that (``verify_forward``, and with it
  speculative decoding; prefixes aliased below ``pbase``) is refused by
  such an adapter's ``bind`` with an error that names the mechanism,
  never served wrong.
- Per-row INDEPENDENCE: row b's logits depend only on row b's tokens and
  frontier. This is what the fleet's crash-replay bit-identity invariant
  (RESILIENCE.md) rests on — replayed requests land in different slots
  next to different neighbors and must emit the same stream. An adapter
  with cross-row coupling (e.g. expert capacity dropping) must neutralize
  it (adapters/decoder.py routes exact top-k with no capacity, so nothing
  couples rows) or document that it breaks the invariant.
"""


class ModelAdapter:
    """Base protocol. Engines call ONLY these methods on the model side.

    Required surface: ``cache_spec`` / ``init_cache`` / ``prefill_append``
    / ``decode_step`` / ``verify_forward`` (plus the drafting pair for
    speculative decode). Optional hooks (``bind``, ``serving_params``,
    ``aux_state``, ``cache_gauges``, ``observe``) have inert defaults.
    """

    name = "adapter"

    # ------------------------------------------------------------------
    # required surface
    # ------------------------------------------------------------------
    def cache_spec(self):
        """Hashable shape/dtype spec of the KV cache: an object with
        ``n_layer / n_head / n_embd / n_positions / dtype /
        layer_norm_epsilon / use_flash_decode`` attributes (the
        ``_GenCfg`` shape the KV pool and mesh sharding helpers key on:
        the layers that hold keys, the heads a token stores and their
        width together) and, for a model with a recurrent state a row,
        ``slot_state`` (module docstring). Must be stable for the
        adapter's lifetime — it is part of the jit static key."""
        raise NotImplementedError

    def init_cache(self, batch, max_len, dtype=None):
        """Zeroed cache dict for ``batch`` rows of plane length
        ``max_len``: k/v planes + per-row ``pos`` [B] frontier."""
        raise NotImplementedError

    def prefill_append(self, params, ids, cache, n_valid=None):
        """Append ``ids`` [B, S] at each row's frontier (chunked-prefill
        primitive). ``n_valid`` [B] marks leading real columns; the
        frontier advances by ``n_valid`` (default S), and a recurrent
        state by exactly those columns. Returns
        (fp32 logits [B, S, V], advanced cache)."""
        raise NotImplementedError

    def decode_step(self, params, tok, cache):
        """Advance every row one token: feed ``tok`` [B] at each row's
        frontier. Returns (fp32 logits [B, V], advanced cache)."""
        raise NotImplementedError

    def verify_forward(self, params, ids, cache):
        """Score ``ids`` [B, S] at each row's frontier WITHOUT advancing
        it (speculative verify; rollback = not moving ``pos``). Returns
        (fp32 logits [B, S, V], cache with pos unchanged). An adapter
        whose rows carry a recurrent state raises: scoring a draft moves
        that state, and its ``bind`` refuses speculation."""
        raise NotImplementedError

    # ------------------------------------------------------------------
    # generation by diffusion over blocks
    # ------------------------------------------------------------------
    # What the engine needs to know of a model whose tokens are NOT made
    # one a pass: its block length (1: next-token, and nothing below is
    # read), the id a still-masked position carries, and a pass. The
    # engine picks its decode scan by ``block_length`` alone
    # (``engine._diffusion_chunk_program`` past 1).
    block_length = 1
    mask_token_id = None

    def block_pass(self, params, ids, cache):
        """One pass over a block: ``ids`` [B, block_length] at each row's
        frontier, which is the block's first position (a masked position
        carries ``mask_token_id``), every position of the block seeing the
        others and all earlier blocks. Keys are written at ``[pos, pos +
        block_length)`` and ``pos`` is returned UNCHANGED: the engine moves
        it by a whole block once the block's tokens are final. Returns
        (fp32 logits [B, block_length, V], read AT each position, no
        shift; the cache)."""
        raise NotImplementedError

    # ------------------------------------------------------------------
    # drafting surface (speculative decode)
    # ------------------------------------------------------------------
    def ngram_draft(self, toks, pos, n, k):
        """Propose [B, k] draft tokens from the token ring ``toks`` [B, T]
        at frontiers ``pos`` [B] (prompt-lookup self-speculation)."""
        raise NotImplementedError

    def accept_counts(self, draft, choices, ok=None):
        """[B] accepted-token counts in 1..K+1 given drafts [B, K] and
        the model's verify choices [B, K+1]."""
        raise NotImplementedError

    # ------------------------------------------------------------------
    # optional hooks
    # ------------------------------------------------------------------
    def bind(self, config, mesh=None):
        """Return the adapter specialized to an engine's InferenceConfig
        and mesh (e.g. honor ``config.use_flash_decode`` and
        ``config.paged_kv``, pick the ring fallback when the mesh carries
        a 'seq' axis). Must return an adapter — ``self`` when nothing
        changes."""
        return self

    def serving_params(self, params):
        """The tree as this adapter's step READS it, made once when the
        engine is built: an adapter whose forward casts a leaf to its compute
        type at every use returns the tree with that leaf already cast (a
        float32 GPT-2 tree otherwise pays the whole cast once a step), and
        leaves what the forward reads as it came. Idempotent, and never
        donates: the caller keeps its tree. The default returns its argument,
        the SAME object (DecoderAdapter: the catalog families' weights are
        made in ``cfg.dtype``, and their float32 leaves are float32 on
        purpose)."""
        return params

    def aux_state(self):
        """Extra pool-resident model state: a dict of ``aux_``-prefixed
        arrays merged into the KV pool at build time and threaded through
        every program (e.g. DecoderAdapter's per-expert routed counts).
        NOT per-slot:
        hierarchy capture/restore skips these keys."""
        return {}

    def cache_gauges(self, pool):
        """Static facts of what ``pool`` holds for this model beside the
        k/v pages, as ``{gauge name: number}``: the engine sets them once a
        pool and repeats them in ``metrics()`` (DecoderAdapter: a window
        group's ring, the readers of a shared plane)."""
        return {}

    def observe(self, snap, registry):
        """Publish adapter gauges from a harvest snapshot (the host copy
        of pool state, including ``aux_`` keys) into a telemetry
        MetricsRegistry. Called once per engine step batch — keep it
        cheap and host-only."""
        return None
