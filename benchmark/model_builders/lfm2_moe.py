"""Configurations of ``"model_type": "lfm2_moe"`` (LFM2-8B-A1B): the program's
config-driven decoder block (``deepspeed_tpu/models/decoder.py``) with gated
short convolution layers (``models/shortconv.py``: a tail of two rows a slot,
the kind's only state) beside grouped-query attention layers with rotary
positions and a QK norm a head over a paged cache as deep as the attention
layers only, leading dense layers and the sigmoid router over ALL the
experts, held whole; its weights from the seed, its plain reference and its
account of the cache. Serving only: it owes what the ``serve`` driver asks
and nothing of training (benchmark/README.md, "What a builder owes").
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness
from benchmark.reference import lfm2_moe as reference

# The exemption, the refusal and the error measures are DeepSeek-V3's
# builder's, and the bookkeeping of the comparisons Kimi Linear's: their
# functions, not copies of them.
kimi = harness.load_by_name("model_builders", "kimi_linear")
shared = kimi.shared

# WHAT HOLDS THE PRECISION THE CONFIGURATION STATES (its ``assumed``: bf16
# convolution tails, a float32 router from the float32 norm, bf16 keys and
# values a token, bf16 expert and dense matrices). The serve driver's one
# limit, the token margin, is held at the logit spread of the other serving
# cells (0.645); it sees a wrong token, a wrong stream or a layer gone astray,
# but not a tail, a cache or a matrix rounded to 8 bits: those move the logits
# by less than the program's bf16 activations do (Granite's builder, PERF.md
# PR 33). So six quantities are held on IDENTICAL inputs, at the cell's
# widths, on the checked sequences: the program's own functions are handed
# what the reference computed and must return what the reference returns.
# Each limit lies between what the sound program reads and what reads when
# the quantity is computed in the precision below (``benchmark/
# probe_lfm2_moe.py`` plants each and reads both; PERF.md, PR 44):
#   tail: the largest relative error (Euclidean, a row) of the two rows a
#     slot keeps of a conv layer's ``v = B * z``, ``shortconv.mixer`` on the
#     reference's normed stream as a prompt and its answer are served (the
#     first ``LANE`` tokens one slice from an empty tail, the rest a token at
#     a time, the tail carried in the type ``shortconv.state_shapes`` gives
#     the pool), against the reference's float32 rows; below: the tail
#     carried in ``float8_e4m3fn``.
#   router: DeepSeek-V3's builder's (the router's logits,
#     ``decoder.router_logits`` against the reference's on the reference's
#     normed stream); below: the matmul in bf16.
#   weights: which experts the program's router keeps and how it weighs
#     them, ``routed.route_grouped`` and ``routed.dispatch`` on the
#     REFERENCE's logits, against the reference's own choice and weights
#     from the same logits (float32 both, so the same experts but at an exact
#     tie; the largest difference of a weight, 0.25 where another expert is
#     kept): the bias that chooses and weighs nothing, the four largest, the
#     renormalisation. It holds the RULE of the choice where ``follow`` below
#     lets the program's stream decide a near-tie; below: the scores from
#     logits rounded to bf16.
#   attention: the largest relative error (Euclidean, a token) of what an
#     attention layer ADDS to the stream, ``decoder.attention_mix`` through
#     the program's own ``CacheAttention`` on a PAGED POOL of 8 stored heads
#     of 64 packed two a lane tile (``program_attention``: the whole sequence
#     a lane slice of ``PROBE_PAGE`` tokens at a time, then one decode step of
#     a row a page; on the chip ``kv_append``, ``prefill_attn`` and
#     ``paged_decode`` at ``g = 2``, ``rep = 4``), against the reference's on
#     the reference's normed stream: the projections, the norm a head, the
#     rotation, what ``kv_append`` stores, the kernels and ``out_proj`` in one
#     number; below: the keys and values rounded to ``float8_e4m3fn`` as they
#     are written.
#   experts: the largest relative error (Euclidean, a token) of what an
#     expert layer ADDS to the stream for ``PROBE_ROWS`` rows (the cell's
#     decode batch) of every checked sequence, ``routed.dispatch`` and
#     ``routed.expert_ffn`` on the reference's normed stream with the experts
#     and weights the REFERENCE kept, against the reference's: the gate, a
#     dropped or doubled expert, the three matrices of every chosen expert
#     and the combine weights in one number; below: the experts' matrices
#     rounded to ``float8_e4m3fn``.
#   dense: the same of the leading dense layer, ``decoder.dense_mix``.
TAIL_LIMIT = 1e-2
ROUTER_LIMIT = shared.ROUTER_LIMIT
WEIGHT_LIMIT = 1e-4
ATTENTION_LIMIT = 1.5e-2
EXPERT_LIMIT = 1.5e-2
DENSE_LIMIT = 1.5e-2
PROBE_PAGE = shared.PROBE_PAGE
# the lane's slice (the cell's ``prefill_chunk``): a prompt is one of them
LANE = 128
# the cell's decode batch (its ``max_slots``): the rows of an expert probe,
# and the rows the replay's step is compiled for
PROBE_ROWS = 128

# WHOSE CHOICE OF EXPERTS THE SERVED TOKENS ARE HELD TO. Every expert is held
# here, so wherever bf16 rounding of the stream makes the program keep another
# expert than the float32 reference, what the layer adds changes, and the
# changed stream flips the layers after it: left alone, two positions of
# three part from the reference in some layer and no served token could be
# compared (PERF.md, PR 44). Two things repair that:
#   THE REFERENCE FOLLOWS THE PROGRAM WHERE ITS OWN CHOICE IS A NEAR-TIE. The
#     checked sequences are REPLAYED through the program's own decode
#     iteration (``replay``: ``decoder.forward`` a token at a time over a
#     paged pool and the tails, ``PROBE_ROWS`` rows as the engine's step has,
#     the served tokens forced) and ``forward`` says which experts it kept
#     (``aux_moe_choice``). Where they are not the reference's, and every
#     expert that changed sides stands within ``FOLLOW_SIGMAS`` standard
#     deviations of the rounding noise from the edge of the choice by the
#     REFERENCE's own scores (``sides``), the reference keeps the program's
#     experts, weighed by its own scores (``reference.logits(follow=)``).
#     An expert that changed sides from further away is NOT followed: the
#     position is held to the reference's own choice and reads over the
#     margin. The RULE of the choice (the bias, the four largest, the
#     weights) is held apart, on identical logits (``router_weight_err``).
#   THE NOISE IS THE REFERENCE'S OWN ROUNDING MODEL, not the program's drift:
#     ``LOGIT_NOISE`` is the rms of a router logit's difference, an expert
#     layer, between the float32 reference and the SAME reference with every
#     value a layer hands on rounded to bf16 (``hyper["round"]``), the
#     rounded run keeping the float32 run's experts so that no flip feeds
#     the next layer (``benchmark/probe_lfm2_moe.py``; my chip runs, seeds
#     4400100 and 4400110, agree to 1%; logits that spread 0.90). The program
#     rounds in more places than the model does (its first expert layer
#     parts from the reference by 0.0091), so its flips reach further than
#     the model's sigma says: of 14,638 (layer, position) pairs where the
#     replay kept other experts (5% of all pairs), 87 had an expert that
#     changed sides from beyond 3.5 sigma, one from beyond 5, the furthest
#     from 5.39 (PERF.md, PR 44): the rule follows to 8.
# What is left exempt by rule: the served run is the engine's step, not the
# replay, and two bf16 executions of one arithmetic may still part at a tie
# closer than their own difference. With every changed choice followed, no
# served token of 18,566 read over the margin (the largest 0.070), 1,748 of
# them within 0.1 sigma of a tie: the replay IS the served arithmetic as far
# as two runs can show. A position whose choice, in some layer, stands within
# ``BAND_SIGMAS`` of the edge is given the served token as the row's largest
# logit (``shared.exempted``), a fifth of the positions, and the note reports
# how many; EVERY OTHER POSITION IS HELD to the driver's margin at the full
# spread.
LOGIT_NOISE = (0.0058, 0.0063, 0.0074, 0.0086, 0.0097, 0.0100, 0.0112,
               0.0122, 0.0133, 0.0135, 0.0145)
FOLLOW_SIGMAS = 8.0
BAND_SIGMAS = 0.25
# the serve driver's ``TOKEN_MARGIN_TOL``, for what the note reports
MARGIN = 0.1
# how far experts that changed sides stood, counted in the note
MARKS = (1, 2, 3.5, 5)

KINDS = {"conv": "shortconv", "full_attention": "attention"}


class Model(object):
    def __init__(self, config):
        from deepspeed_tpu.models import decoder

        if "shortconv" not in getattr(decoder, "RECURRENT", ()):
            raise RuntimeError(
                "this program has no gated short convolution "
                "(deepspeed_tpu/models/shortconv.py): it cannot build "
                "model_type lfm2_moe")
        for key, published in (
                ("conv_bias", False), ("norm_topk_prob", True),
                ("use_expert_bias", True)):
            if config[key] != published:
                raise ValueError("model_builders/lfm2_moe.py builds "
                                 "{}={!r} only".format(key, published))
        n_layer = config["num_hidden_layers"]
        if len(config["layer_types"]) != n_layer \
                or not set(config["layer_types"]) <= set(KINDS):
            raise ValueError("layer_types names conv or full_attention for "
                             "each of the {} layers".format(n_layer))
        n_head = config["num_attention_heads"]
        if config["hidden_size"] % n_head:
            raise ValueError("a head is hidden_size / num_attention_heads")
        self.cfg = decoder.DecoderConfig(
            vocab_size=config["vocab_size"], n_layer=n_layer, n_head=n_head,
            head_dim=config["hidden_size"] // n_head,
            hidden_size=config["hidden_size"],
            n_positions=config["max_position_embeddings"],
            n_experts=config["num_experts"],
            experts_per_token=config["num_experts_per_tok"],
            expert_width=config["moe_intermediate_size"],
            rms_norm_eps=config["norm_eps"],
            rope_theta=float(config["rope_theta"]), qk_norm="head",
            norm_topk_prob=config["norm_topk_prob"],
            tie_word_embeddings=True,
            dtype=jnp.dtype(config["deployment"]["compute_dtype"]),
            initializer_range=config["initializer_range"],
            n_kv_head=config["num_key_value_heads"],
            layer_types=tuple(KINDS[k] for k in config["layer_types"]),
            dense_layers=config["num_dense_layers"],
            dense_width=config["intermediate_size"],
            router_scoring="sigmoid", n_group=1, topk_group=1,
            routed_scaling=float(config["routed_scaling_factor"]),
            shortconv_kernel=config["conv_L_cache"])
        self.module = decoder.DecoderLM(self.cfg)
        # the benchmark's own choice of its random weights' scale (the
        # file's ``assumed``): nothing a served model has
        self.embed_range = float(config.get("embed_init_range",
                                            config["initializer_range"]))
        self.final_norm = float(config.get("final_norm_init", 1.0))
        self.bias_range = float(config.get("router_bias_init_range", 0.0))
        self.n_layer, self.n_head = self.cfg.n_layer, n_head
        self.head_dim = self.cfg.head_dim
        self.vocab_size = self.cfg.vocab_size

    def sizes(self):
        from deepspeed_tpu.inference.kv_pool import slot_state_nbytes
        from deepspeed_tpu.models.decoder import cache_spec

        c = self.cfg
        conv = c.hidden_size * 3 * c.hidden_size \
            + c.shortconv_kernel * c.hidden_size \
            + c.hidden_size * c.hidden_size
        attention = 2 * c.hidden_size * c.n_embd \
            + 2 * c.hidden_size * c.n_kv * c.head_dim + 2 * c.head_dim
        dense = 3 * c.hidden_size * c.dense_width
        experts = c.hidden_size * c.n_experts + c.n_experts \
            + c.n_experts * 3 * c.hidden_size * c.expert_width
        spec = cache_spec(c)
        return {"num_hidden_layers": c.n_layer, "hidden_size": c.hidden_size,
                "layer_types": list(c.kinds), "heads": c.n_head,
                "kv_heads": c.n_kv, "head_dim": c.head_dim,
                "conv_L_cache": c.shortconv_kernel,
                "num_dense_layers": c.dense_layers,
                "intermediate_size": c.dense_width,
                "num_experts": c.n_experts,
                "num_experts_per_tok": c.experts_per_token,
                "moe_intermediate_size": c.expert_width,
                "vocab_size": c.vocab_size, "kv_layers": spec.n_layer,
                "state_bytes_per_slot": slot_state_nbytes(spec),
                "params": len(c.shortconv_layers) * conv
                + len(c.kv_layers) * attention + c.dense_layers * dense
                + (c.n_layer - c.dense_layers) * experts
                + 2 * c.n_layer * c.hidden_size
                + c.vocab_size * c.hidden_size + c.hidden_size}

    def init_params(self, seed, on_host=False):
        """Random weights from the seed in the type they are served in, made
        in one jitted program on the default device. The seed is an argument
        of that program, so that one cached program serves every seed."""
        return jax.jit(lambda key: rescaled(
            self.module.init(key)["params"], key, self.embed_range
            / self.cfg.initializer_range, self.final_norm, self.bias_range))(
            jax.random.PRNGKey(seed))

    def kv_bytes_per_token_layer(self):
        """A key and a value for every STORED head, in the type the engine
        stores, in a layer that holds keys (3 of the 12 here)."""
        return 2 * self.cfg.n_kv * self.head_dim * self.cfg.dtype.itemsize

    def reference_logits(self, params, ids):
        """The reference's logits for the served streams ``ids`` (module
        comment above): the program's experts followed where the reference's
        own choice is a near-tie, the served token made the row's choice in
        the band that stays exempt, and the comparisons on identical inputs
        made on the way: where one fails, no token of the logits returned is
        within the driver's margin, so the run is not ``correct``."""
        ids = np.asarray(ids)
        held = Precision(params, self.cfg, replay(params, self.cfg, ids))
        out = reference_logits(params, ids, self.cfg, watch=held.watch,
                               follow=held.follow)
        # what the serve driver's fixed margin is worth here (PERF.md)
        harness.note(event="reference_logits", shape=list(out.shape),
                     std_over_vocab=float(out[0].std(axis=-1).mean()),
                     std=float(out[0].std()))
        ties = held.ties(ids.shape)
        harness.note(
            event="precision", held=held.ok(),
            limits=dict(Precision.LIMITS), follow_sigmas=FOLLOW_SIGMAS,
            band_sigmas=BAND_SIGMAS, positions=int(ties.size),
            exempt_positions=int(ties.sum()), **held.routing(),
            **served_margins(out, ids, ties, held.nearest(ids.shape)),
            **held.readings())
        return shared.exempted(out, ids, ties) if held.ok() \
            else shared.refused(out, ids)


def served_margins(out, ids, ties, near):
    """What the note says of the margins, from the logits BEFORE the
    exemption: over the positions that surely predict a served token (past
    the longest prompt, ``LANE``, and before the row's last token; a row
    ends where its padding of zeros starts), how many the margin holds, the
    largest margin it reads there and how many read over ``MARGIN``; and
    what the exempt band hides: the misses in it and how far from the edge
    (``near``, in standard deviations) the furthest miss of all stood, which
    is what ``BAND_SIGMAS`` has to cover."""
    b, t = ids.shape
    nxt = np.roll(ids, -1, axis=1)
    margin = out.max(-1) - np.take_along_axis(out, nxt[..., None], -1)[..., 0]
    length = np.where(ids.any(1), t - np.argmax(ids[:, ::-1] != 0, axis=1), 0)
    at = np.arange(t)[None]
    served = (at >= LANE - 1) & (at < length[:, None] - 1)
    held, miss = served & ~ties, served & (margin > MARGIN)
    return {"served_positions": int(served.sum()),
            "held_positions": int(held.sum()),
            "max_margin_held": float(margin[held].max()) if held.any()
            else None,
            "held_over_margin": int((miss & ~ties).sum()),
            "exempt_over_margin": int((miss & ties).sum()),
            "furthest_miss_sigmas": float(near[miss].max()) if miss.any()
            else None}


def rescaled(params, key, table, last_norm, bias):
    """``params`` with the tied token table times ``table``, the last norm's
    weight at ``last_norm`` and every expert layer's selection bias normal
    at ``bias`` from the seed: where the benchmark sets the spread of its
    random weights' logits and makes the bias tell choosing from weighting
    (the configuration's ``embed_init_range``, ``final_norm_init`` and
    ``router_bias_init_range``, with their reasons under ``assumed``)."""
    moe = params["moe"]
    return dict(
        params, embed=params["embed"] * table,
        final_norm=params["final_norm"] * last_norm,
        moe=dict(moe, router_bias=bias * jax.random.normal(
            jax.random.fold_in(key, 38), moe["router_bias"].shape,
            jnp.float32)))


class Precision(kimi.Precision):
    """The comparisons of the module comment, fed by the reference's
    ``watch`` a layer and a sequence at a time, and whose experts the
    reference keeps (``follow``); ``ties``, ``readings`` and ``ok`` are Kimi
    Linear's builder's, over these limits. ``choices``: the experts the
    program's replay kept [expert layers, B, T, k], or None: the reference
    keeps its own and only the band is marked."""

    LIMITS = (("tail_rel_err", TAIL_LIMIT),
              ("router_logit_err", ROUTER_LIMIT),
              ("router_weight_err", WEIGHT_LIMIT),
              ("attention_rel_err", ATTENTION_LIMIT),
              ("expert_rel_err", EXPERT_LIMIT),
              ("dense_rel_err", DENSE_LIMIT))

    def __init__(self, params, cfg, choices=None):
        super().__init__(params, cfg)
        self.choices = choices
        self.near = {}                       # sequence -> [T] float
        self.parted = {"differ": 0, "followed": 0, "not_followed": 0,
                       "furthest_sigmas": 0.0,
                       "beyond_sigmas": {str(m): 0 for m in MARKS}}

    def follow(self, layer, sequence, router_logits):
        """``reference.logits``'s ``follow`` (module comment, WHOSE CHOICE):
        the experts the reference is to keep [T, k], and the band marked."""
        cfg, at = self.cfg, layer - self.cfg.dense_layers
        inside, far = sides(
            np.asarray(router_logits),
            np.asarray(self.params["moe"]["router_bias"][at]),
            cfg.experts_per_token, LOGIT_NOISE[min(at, len(LOGIT_NOISE) - 1)])
        near = far.min(-1)
        self.near[sequence] = np.minimum(self.near.get(sequence, np.inf),
                                         near)
        self.tied[sequence] = self.tied.get(sequence, False) \
            | (near < BAND_SIGMAS)
        if self.choices is None:
            return None
        theirs = np.zeros_like(inside)
        np.put_along_axis(theirs, self.choices[at, sequence], True, axis=-1)
        moved = theirs != inside
        worst = np.where(moved, far, 0.0).max(-1)
        differ = moved.any(-1)
        followed = differ & (worst < FOLLOW_SIGMAS)
        self.parted["differ"] += int(differ.sum())
        self.parted["followed"] += int(followed.sum())
        self.parted["not_followed"] += int((differ & ~followed).sum())
        self.parted["furthest_sigmas"] = max(self.parted["furthest_sigmas"],
                                             float(worst.max()))
        for mark in MARKS:
            self.parted["beyond_sigmas"][str(mark)] += int(
                (worst >= mark).sum())
        own = np.argsort(~inside, axis=-1, kind="stable")[
            :, :cfg.experts_per_token]
        return np.where(followed[:, None], self.choices[at, sequence], own)

    def routing(self):
        """What the note says of the experts followed: the (layer,
        position) pairs where the replay kept other experts than the
        reference's scores, how many of them the reference followed, how
        far from the edge the furthest expert that changed sides stood, and
        how many pairs had one beyond each of ``MARKS``."""
        return dict(self.parted, replayed=self.choices is not None)

    def nearest(self, shape):
        """[B, T] float: how near, in standard deviations, a position's
        choice stands to the edge in its nearest layer."""
        out = np.full(shape, np.inf)
        for b, near in self.near.items():
            out[b] = near
        return out

    def watch(self, layer, sequence, seen):
        from deepspeed_tpu.models import decoder

        cfg = self.cfg
        if cfg.kinds[layer] == "shortconv":
            weights = {k: v[cfg.shortconv_layers.index(layer)]
                       for k, v in self.params["shortconv"].items()}
            self.seen["tail_rel_err"].append(shared.latent_error(
                program_tail(weights, cfg, seen["mix_in"]), seen["tail"]))
        else:
            weights = {k: v[cfg.kv_layers.index(layer)]
                       for k, v in self.params["attn"].items()}
            self.seen["attention_rel_err"].extend(
                shared.latent_error(got, seen["mix_out"][at])
                for got, at in program_attention(weights, cfg,
                                                 seen["mix_in"]))
        t = seen["ffn_in"].shape[0]
        rows = (np.arange(PROBE_ROWS) * t) // PROBE_ROWS
        if "router_logits" not in seen:
            self.seen["dense_rel_err"].append(shared.latent_error(
                program_dense(self.params["dense"], cfg, layer,
                              seen["ffn_in"][rows]), seen["ffn_out"][rows]))
            return
        at = layer - cfg.dense_layers
        self.seen["router_logit_err"].append(shared.router_error(
            decoder.router_logits(seen["ffn_in"],
                                  self.params["moe"]["router"][at]),
            seen["router_logits"]))
        self.seen["router_weight_err"].append(shared.router_error(
            program_kept(self.params["moe"]["router_bias"][at], cfg,
                         seen["router_logits"]), own_kept(
                seen["router_logits"],
                np.asarray(self.params["moe"]["router_bias"][at]), cfg)))
        self.seen["expert_rel_err"].append(shared.latent_error(
            program_experts(self.params["moe"], cfg, at, seen["ffn_in"][rows],
                            seen["kept"][rows]), seen["ffn_out"][rows]))


def sides(logits, bias, k, noise):
    """From the REFERENCE's router logits [T, E] (float32) and the selection
    bias alone: (which experts the scores keep [T, E] bool; how far, in
    standard deviations of a logit's noise ``noise``, each expert stands
    from CHANGING SIDES [T, E]). A score is ``sigmoid(logit) + bias``, so a
    logit's noise moves it by the sigmoid's slope ``s (1 - s)``; a kept
    expert changes sides by falling under the best score left out, one left
    out by passing the last score kept, and the gap between the two scores
    is measured against the root of their two slopes' squares
    (``model_builders/deepseek_v3.py`` ``tie_distance``, for one group with
    every expert held)."""
    rows = np.arange(logits.shape[0])[:, None]
    score = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    slope, choose = score * (1.0 - score), score + bias[None]
    order = np.argsort(-choose, axis=-1)
    last_in, first_out = order[:, k - 1:k], order[:, k:k + 1]
    inside = np.zeros(logits.shape, bool)
    np.put_along_axis(inside, order[:, :k], True, axis=-1)
    edge = np.where(inside, first_out, last_in)
    far = np.abs(choose - choose[rows, edge]) / (
        noise * np.sqrt(slope ** 2 + slope[rows, edge] ** 2))
    return inside, far


@functools.lru_cache(maxsize=None)
def _replay(cfg, rows, n_seq, t):
    """``replay`` as one program: (params, ids [n_seq, t]) -> the experts
    kept [t, expert layers, n_seq, k]."""
    from deepspeed_tpu.models import decoder
    from deepspeed_tpu.ops.transformer.kernels.decode_attention import \
        lane_pack

    cfg = decoder.served_config(cfg)
    n_pg = -(-t // PROBE_PAGE)
    g = lane_pack(cfg.head_dim, cfg.n_kv)
    live = (jnp.arange(rows) < n_seq).astype(jnp.int32)
    ask = jnp.zeros((cfg.n_layer - cfg.dense_layers, rows, 1,
                     cfg.experts_per_token), jnp.int32)

    def run(params, ids):
        arena = jnp.zeros((len(cfg.kv_layers), 1 + n_seq * n_pg,
                           -(-cfg.n_kv // g), PROBE_PAGE, g * cfg.head_dim),
                          cfg.dtype)
        pages = 1 + jnp.arange(n_seq * n_pg, dtype=jnp.int32)
        cache = {"k": arena, "v": arena,
                 "pos": jnp.zeros((rows,), jnp.int32),
                 "block_tbl": jnp.zeros((rows, n_pg), jnp.int32).at[
                     :n_seq].set(pages.reshape(n_seq, n_pg))}
        for name, shape, dtype in decoder.cache_spec(cfg).slot_state:
            cache[name] = jnp.zeros((rows,) + tuple(shape), dtype)

        def step(cache, tok):
            _, new = decoder.forward(params, cfg, tok[:, None], dict(
                cache, n_valid=live, aux_moe_choice=ask))
            kept = new.pop("aux_moe_choice")
            new["pos"] = jnp.where(live > 0, new["pos"], cache["pos"])
            return new, kept[:, :n_seq, 0]

        toks = jnp.zeros((t, rows), jnp.int32).at[:, :n_seq].set(ids.T)
        return jax.lax.scan(step, cache, toks)[1]

    return jax.jit(run)


def replay(params, cfg, ids, rows=PROBE_ROWS):
    """The experts the PROGRAM keeps for every token of the sequences
    ``ids`` [B, T], a layer: [expert layers, B, T, k] on the host. The
    program's own decode iteration (``decoder.forward`` on one token a row,
    as ``adapter.decode_step`` calls it, over a paged pool of its own with
    page 0 the trash page, a table a row, and the rows' tails from zeros)
    with ``rows`` rows of which the first ``B`` carry the sequences and the
    others stand idle as an engine's empty slots do (``n_valid`` 0, the
    trash page), the tokens of ``ids`` forced."""
    b, t = ids.shape
    kept = _replay(cfg, max(rows, b), b, t)(params, jnp.asarray(ids,
                                                              jnp.int32))
    return np.moveaxis(np.asarray(kept), 0, 2)


@functools.partial(jax.jit, static_argnames=("cfg",))
def program_kept(bias, cfg, router_logits):
    """The weights [T, E] the PROGRAM's router keeps from the reference's
    logits (0 for an expert that was not chosen): ``routed.route_grouped``
    as ``decoder.moe`` calls it, spread by ``routed.dispatch``."""
    from deepspeed_tpu.moe import routed

    weights, experts = routed.route_grouped(
        router_logits, bias, cfg.experts_per_token, cfg.n_group,
        cfg.topk_group, cfg.routed_scaling, cfg.norm_topk_prob)
    first, held = cfg.held
    return routed.dispatch(weights, experts, held, first)[0]


def own_kept(router_logits, bias, cfg):
    """The same from the reference's ``keep``: its own choice."""
    return reference.keep(router_logits, bias, reference._static(hyper(cfg)))


@functools.partial(jax.jit, static_argnames=("cfg",))
def program_experts(stacks, cfg, at, ffn_in, kept):
    """What the PROGRAM's expert layer ``at`` adds to the stream for the
    rows ``ffn_in`` [R, C] (the reference's normed stream, cast to the
    compute type) with the experts and weights the reference kept
    (``kept`` [R, E], 0 elsewhere) handed to ``routed.dispatch`` as a router
    hands them: [R, C] float32."""
    from deepspeed_tpu.moe import routed

    first, held = cfg.held
    weights, experts = jax.lax.top_k(kept, cfg.experts_per_token)
    gate, _ = routed.dispatch(weights, experts.astype(jnp.int32), held,
                              first)
    return routed.expert_ffn(
        ffn_in.astype(cfg.dtype), gate, stacks["w_gate_up"][at],
        stacks["w_down"][at]).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("cfg",))
def program_dense(stacks, cfg, layer, ffn_in):
    """The same of leading dense layer ``layer``: ``decoder.dense_mix``."""
    from deepspeed_tpu.models import decoder

    return decoder.dense_mix(
        {k: v[layer] for k, v in stacks.items()}, cfg,
        ffn_in.astype(cfg.dtype)).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("cfg", "dtype", "lane"))
def _tail(weights, mix_in, cfg, dtype, lane):
    from deepspeed_tpu.models import shortconv

    (_, shape, _), = [s for s in shortconv.state_shapes(cfg)
                      if s[0] == shortconv.state_key(0)]
    h = mix_in[None].astype(cfg.dtype)
    _, tail = shortconv.mixer(
        weights, cfg, h[:, :lane], jnp.zeros((1,) + tuple(shape), dtype),
        jnp.zeros((1,), jnp.int32), jnp.asarray([lane], jnp.int32))

    def token(tail, x):
        h_t, pos = x
        _, tail = shortconv.mixer(weights, cfg, h_t[:, None], tail, pos[None],
                                  jnp.ones((1,), jnp.int32))
        return tail, None

    rest = h.shape[1] - lane
    tail, _ = jax.lax.scan(token, tail, (
        jnp.moveaxis(h[:, lane:], 1, 0), lane + jnp.arange(rest)))
    return tail[0].astype(jnp.float32)


def program_tail(weights, cfg, mix_in, dtype=None):
    """The two rows a slot would keep of one conv layer's ``v``, from the
    reference's normed stream ``mix_in`` [T, C] (cast to the compute type,
    as the program's own norm hands it on) through ``shortconv.mixer`` as a
    request is served (module comment): [K - 1, C] float32. ``dtype``: the
    type the tail is carried in, the pool's own unless given."""
    from deepspeed_tpu.models import shortconv

    (_, _, tail_dtype), = [s for s in shortconv.state_shapes(cfg)
                           if s[0] == shortconv.state_key(0)]
    return _tail(weights, mix_in, cfg, jnp.dtype(dtype or tail_dtype),
                 min(LANE, mix_in.shape[0] // 2))


@functools.lru_cache(maxsize=None)
def _mix(cfg, name):
    """``decoder.attention_mix`` of one layer through the program's own
    ``CacheAttention`` on a paged pool of that one layer, as one program:
    (weights, h [B, S, C], the pool's two arenas, its table, the rows'
    frontiers) -> (y [B, S, C] float32, the arenas written)."""
    from deepspeed_tpu.models import decoder, generation

    def run(weights, h, k, v, tbl, pos):
        attend = generation.CacheAttention(
            cfg, {"k": k, "v": v, "pos": pos, "block_tbl": tbl}, h.shape[1],
            name)
        rope = decoder.rope_angles(attend.q_pos, cfg.head_dim,
                                   cfg.rope_theta)
        y, (k, v) = decoder.attention_mix(weights, cfg, h, 0, rope, attend,
                                          attend.planes)
        return y.astype(jnp.float32), k, v

    return jax.jit(run, donate_argnums=(2, 3))


def program_attention(weights, cfg, mix_in):
    """What the PROGRAM's attention adds to the stream for one sequence
    through a paged pool as the engine holds one (the stored heads packed as
    ``lane_pack`` packs them, page 0 the trash page, a table a row): first
    the whole sequence as the LANE serves a prompt, a slice of
    ``PROBE_PAGE`` tokens at a time, then ONE DECODE STEP of one row a page
    (``model_builders/deepseek_v3.py`` ``program_attention``, whose walk
    this is): [(y [n, C] float32, the positions it stands for)]."""
    from deepspeed_tpu.models import decoder
    from deepspeed_tpu.ops.transformer.kernels.decode_attention import \
        lane_pack

    cfg = decoder.served_config(cfg)
    t = mix_in.shape[0]
    n_lp = -(-t // PROBE_PAGE)
    h = jnp.pad(mix_in, ((0, n_lp * PROBE_PAGE - t), (0, 0))).astype(
        cfg.dtype)
    g = lane_pack(cfg.head_dim, cfg.n_kv)
    k, v = (jnp.zeros((1, n_lp + 1, -(-cfg.n_kv // g), PROBE_PAGE,
                       g * cfg.head_dim), cfg.dtype) for _ in "kv")
    tbl = 1 + jnp.arange(n_lp, dtype=jnp.int32)[None]
    lane, step = _mix(cfg, "prefill_attn"), _mix(cfg, None)
    out = []
    for j in range(n_lp):
        y, k, v = lane(weights, h[None, j * PROBE_PAGE:(j + 1) * PROBE_PAGE],
                       k, v, tbl, jnp.asarray([j * PROBE_PAGE], jnp.int32))
        out.append(y[0])
    rows = np.minimum(np.arange(n_lp) * PROBE_PAGE
                      + (37 * np.arange(n_lp) + 11) % PROBE_PAGE, t - 1)
    y, k, v = step(weights, h[rows][:, None], k, v,
                   jnp.tile(tbl, (n_lp, 1)), jnp.asarray(rows, jnp.int32))
    return [(jnp.concatenate(out)[:t], np.arange(t)), (y[:, 0], rows)]


def retrace():
    """Drop the compiled probes and the replay: a caller that plants another
    precision in the program (``benchmark/probe_lfm2_moe.py``) has them
    traced again."""
    _mix.cache_clear()
    _replay.cache_clear()
    for compiled in (_tail, program_kept, program_experts, program_dense):
        compiled.clear_cache()


def published_names(params, cfg):
    """The program's tree under the reference's names: ``wqkv`` cut into the
    three projections it holds, the dense and the experts' ``w_gate_up`` into
    ``w1`` (the gate) and ``w3``. ``layers`` is a generator: one layer's
    slices exist at a time, and of its experts one expert's (``Experts``)."""
    f, fd = cfg.expert_width, cfg.dense_width
    q_w, kv_w = cfg.n_embd, cfg.n_kv * cfg.head_dim

    def layers():
        n = {"shortconv": 0, "attention": 0}
        for i, kind in enumerate(cfg.kinds):
            out = {"operator_norm": params["layers"]["attn_norm"][i],
                   "ffn_norm": params["layers"]["ffn_norm"][i]}
            if kind == "shortconv":
                a = {k: v[n[kind]] for k, v in params["shortconv"].items()}
                out.update(in_proj=a["in_proj"], conv=a["conv_w"],
                           out_proj=a["out_proj"])
            else:
                a = {k: v[n[kind]] for k, v in params["attn"].items()}
                out.update(q_proj=a["wqkv"][:, :q_w],
                           k_proj=a["wqkv"][:, q_w:q_w + kv_w],
                           v_proj=a["wqkv"][:, q_w + kv_w:],
                           out_proj=a["wo"], q_layernorm=a["q_norm"],
                           k_layernorm=a["k_norm"])
            n[kind] += 1
            if i < cfg.dense_layers:
                dense = params["dense"]
                out.update(w1=shared.Window(dense["w_gate_up"], i, 0, fd),
                           w3=shared.Window(dense["w_gate_up"], i, fd, fd),
                           w2=shared.Window(dense["w_down"], i))
            else:
                at, stacks = i - cfg.dense_layers, params["moe"]
                out.update(
                    gate=stacks["router"][at],
                    expert_bias=stacks["router_bias"][at],
                    w1=shared.Experts(stacks["w_gate_up"], at, slice(0, f)),
                    w3=shared.Experts(stacks["w_gate_up"], at,
                                      slice(f, 2 * f)),
                    w2=shared.Experts(stacks["w_down"], at, slice(None)))
            yield out

    return {"embed_tokens": params["embed"], "layers": layers(),
            "norm": params["final_norm"]}


def hyper(cfg):
    """What the reference is told beside the weights."""
    back = {v: k for k, v in KINDS.items()}
    return {"layer_types": tuple(back[k] for k in cfg.kinds),
            "n_head": cfg.n_head, "n_kv": cfg.n_kv, "theta": cfg.rope_theta,
            "eps": cfg.rms_norm_eps, "top_k": cfg.experts_per_token,
            "norm_topk_prob": cfg.norm_topk_prob,
            "routed_scaling_factor": cfg.routed_scaling}


def reference_logits(params, ids, cfg, watch=None, follow=None, round=None):
    """The plain reference on the program's parameter tree, for a
    ``DecoderConfig`` ``cfg`` (the tests call it at a tiny size); ``round``:
    its rounding model's type (``LOGIT_NOISE``)."""
    return reference.logits(published_names(params, cfg), ids,
                            dict(hyper(cfg), round=round), watch=watch,
                            follow=follow)
