"""Configurations of ``"model_type": "deepseek_v3"`` (DeepSeek-V3, R1, V3.1):
the program's config-driven decoder block (``deepspeed_tpu/models/decoder.py``)
with latent attention (MLA) over a one-plane cache, leading dense layers and
the group-limited sigmoid router over a chip's share of the experts, built
from the published keys and the share the file states; its weights from the
seed, its plain reference and its account of the cache. Serving only: it
owes what the ``serve`` driver asks and nothing of training
(benchmark/README.md, "What a builder owes").
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import deepseek_v3 as reference

# WHAT HOLDS THE PRECISION THE CONFIGURATION STATES (its ``assumed``: a float32
# router from the float32 norm; a bf16 latent a token; bf16 matmuls that sum in
# float32). The serve driver's one limit, the token margin, is held at the
# logit spread of the other serving cells (0.645); it sees a wrong token, a
# wrong stream or a layer gone astray, but not a router that picks another
# expert for a token in a hundred, nor a cache rounded to 8 bits: those move
# the logits by less than the program's bf16 activations do. So, as Granite's
# builder does for its state and router, three quantities are held on
# IDENTICAL inputs, at the cell's widths, on the checked sequences: the
# program's own functions are handed what the reference computed and must
# return what the reference returns. Each limit lies between what the sound
# program reads and what reads when the quantity is computed in the precision
# below (PERF.md, PR 38, has both readings of each):
#   router: the largest difference of a router logit,
#     ``decoder.router_logits`` against the reference's on the reference's
#     normed stream; below: the matmul in bf16.
#   latent: the largest relative error (Euclidean, a token) of what
#     ``decoder.latent_token`` would cache, ``[c_kv | k_r]`` after the norm
#     and the rotation, against the reference's on the reference's normed
#     stream; below: the cached token rounded to ``float8_e4m3fn``.
#   attention: the largest relative error (Euclidean, a token) of what the
#     layer ADDS to the stream, ``decoder.latent_mix`` through the program's
#     own ``CacheAttention`` on a PAGED LATENT POOL (``program_attention``:
#     the whole sequence a lane slice of ``PROBE_PAGE`` tokens at a time as a
#     prompt is served, then one decode step of a row a page as the scan
#     serves them; on the chip ``kv_append`` and the ``latent_decode`` kernel
#     under both of its names), against the reference's EXPANDED attention on
#     the reference's normed stream: the queries' projections, the absorbed
#     products, what ``kv_append`` stores, the kernel, the softmax scale and
#     ``o_proj`` in one number; below: the page written rounded to
#     ``float8_e4m3fn`` (in ``CacheAttention._latent``, before ``kv_append``).
ROUTER_LIMIT = 3e-4
LATENT_LIMIT = 1e-2
ATTENTION_LIMIT = 1.9e-2
PROBE_PAGE = 128
# What a failed comparison adds to a logit no stream served: the driver's
# margin then reads this, far from anything rounding gives.
REFUSED = 1e3

# WHERE THE REFERENCE'S OWN CHOICE IS A NEAR-TIE, EITHER CHOICE IS THE MODEL'S.
# bf16 rounding of the residual stream moves a router LOGIT by 0.026 (first
# expert layer) to 0.046 (fifth), rms (``LOGIT_NOISE``; the program beside
# the reference on the same 4 x 1,024 tokens, PERF.md, PR 38). Where the
# float32 reference's own scores leave the choice of a HELD expert within
# that noise, the program may keep another held expert than the reference
# (5% of tokens in some layer), and with sigmoid scores renormalised x 2.5
# one expert of random weights moves that token's logits by up to 0.64 of
# their spread: no rounding, and not a fault either. No replay can find the
# served run's own choice, so such positions are EXEMPT BY RULE, from the
# reference's scores alone (``tie_distance``, in standard deviations of that
# noise; every one of the 206 flips measured stood within 1.65, and a rule
# at ``TIE_SIGMAS`` exempts 45% of positions: one that covers a flip in
# 10,000 must exempt some eight times as many positions as flip): the served
# token is given the row's largest logit there, and ``note`` reports how
# many positions that was. Every other position is held to the driver's
# margin at the full spread.
LOGIT_NOISE = (0.026, 0.030, 0.035, 0.040, 0.046)   # expert layer 1, 2, ..
TIE_SIGMAS = 3.5


class Model(object):
    def __init__(self, config):
        from deepspeed_tpu.models.decoder import DecoderConfig, DecoderLM

        for key, published in (
                ("attention_bias", False), ("hidden_act", "silu"),
                ("moe_layer_freq", 1), ("scoring_func", "sigmoid"),
                ("topk_method", "noaux_tc"), ("tie_word_embeddings", False),
                ("num_nextn_predict_layers", 0)):
            if config[key] != published:
                raise ValueError("model_builders/deepseek_v3.py builds "
                                 "{}={!r} only".format(key, published))
        if config["num_key_value_heads"] != config["num_attention_heads"]:
            raise ValueError("latent attention gives every query head a "
                             "key and a value of its own")
        yarn = config["rope_scaling"]
        if yarn is not None and yarn["type"] != "yarn":
            raise ValueError("rope_scaling is YaRN's or none")
        first, held = config.get("experts_held",
                                 (0, config["n_routed_experts"]))
        published = config.get("router_outputs", config["n_routed_experts"])
        if held != config["n_routed_experts"] or first + held > published:
            raise ValueError("n_routed_experts counts the experts held")
        n_head = config["num_attention_heads"]
        self.cfg = DecoderConfig(
            vocab_size=config["vocab_size"],
            n_layer=config["num_hidden_layers"], n_head=n_head,
            head_dim=config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
            hidden_size=config["hidden_size"],
            n_positions=config["max_position_embeddings"],
            n_experts=published,
            experts_per_token=config["num_experts_per_tok"],
            expert_width=config["moe_intermediate_size"],
            rms_norm_eps=config["rms_norm_eps"],
            rope_theta=float(config["rope_theta"]), qk_norm=False,
            norm_topk_prob=config["norm_topk_prob"],
            tie_word_embeddings=False,
            dtype=jnp.dtype(config["deployment"]["compute_dtype"]),
            initializer_range=config["initializer_range"],
            shared_width=config["n_shared_experts"]
            * config["moe_intermediate_size"],
            experts_held=None if held == published else (first, held),
            kv_lora_rank=config["kv_lora_rank"],
            q_lora_rank=config["q_lora_rank"],
            qk_nope_dim=config["qk_nope_head_dim"],
            qk_rope_dim=config["qk_rope_head_dim"],
            v_head_dim=config["v_head_dim"],
            rope_yarn=None if yarn is None else (
                float(yarn["factor"]),
                int(yarn["original_max_position_embeddings"]),
                float(yarn["beta_fast"]), float(yarn["beta_slow"]),
                float(yarn["mscale"]), float(yarn["mscale_all_dim"])),
            dense_layers=config["first_k_dense_replace"],
            dense_width=config["intermediate_size"],
            router_scoring="sigmoid", n_group=config["n_group"],
            topk_group=config["topk_group"],
            routed_scaling=float(config["routed_scaling_factor"]))
        self.module = DecoderLM(self.cfg)
        # the benchmark's own choice of its random weights' scale (the
        # file's ``assumed``): nothing a served model has
        self.head_range = float(config.get("lm_head_init_range",
                                           config["initializer_range"]))
        self.bias_range = float(config.get("router_bias_init_range", 0.0))
        self.n_layer, self.n_head = self.cfg.n_layer, n_head
        self.head_dim = self.cfg.head_dim
        self.vocab_size = self.cfg.vocab_size

    def sizes(self):
        c = self.cfg
        attention = (c.hidden_size * c.q_lora_rank + c.q_lora_rank
                     + c.q_lora_rank * c.n_head * c.head_dim
                     + c.hidden_size * (c.kv_lora_rank + c.qk_rope_dim)
                     + c.kv_lora_rank + c.kv_lora_rank * c.n_head
                     * (c.qk_nope_dim + c.v_head_dim)
                     + c.n_head * c.v_head_dim * c.hidden_size
                     + 2 * c.hidden_size)
        dense = 3 * c.hidden_size * c.dense_width
        experts = (c.hidden_size * c.n_experts + c.n_experts
                   + c.held[1] * 3 * c.hidden_size * c.expert_width
                   + 3 * c.hidden_size * c.shared_width)
        return {"num_hidden_layers": c.n_layer, "hidden_size": c.hidden_size,
                "heads": c.n_head, "qk_nope_head_dim": c.qk_nope_dim,
                "qk_rope_head_dim": c.qk_rope_dim,
                "v_head_dim": c.v_head_dim, "kv_lora_rank": c.kv_lora_rank,
                "q_lora_rank": c.q_lora_rank,
                "first_k_dense_replace": c.dense_layers,
                "intermediate_size": c.dense_width,
                "router_outputs": c.n_experts, "experts_held": list(c.held),
                "num_experts_per_tok": c.experts_per_token,
                "moe_intermediate_size": c.expert_width,
                "vocab_size": c.vocab_size,
                "softmax_scale": reference.softmax_scale(hyper(self.cfg)),
                "latent_stored_width": c.latent_width,
                "params": c.n_layer * attention + c.dense_layers * dense
                + (c.n_layer - c.dense_layers) * experts
                + 2 * c.vocab_size * c.hidden_size + c.hidden_size}

    def init_params(self, seed, on_host=False):
        """Random weights from the seed in the type they are served in, made
        in one jitted program on the default device. The seed is an argument
        of that program, so that one cached program serves every seed."""
        return jax.jit(lambda key: rescaled(
            self.module.init(key)["params"], key, self.head_range
            / self.cfg.initializer_range, self.bias_range))(
            jax.random.PRNGKey(seed))

    def kv_bytes_per_token_layer(self):
        """What MUST be read of a cached token in one layer: the compressed
        latent and the one rotary key, in the type the engine stores;
        whatever pad the pool stores beside them (640 for 576) is the
        program's cost, not the algorithm's."""
        return (self.cfg.kv_lora_rank + self.cfg.qk_rope_dim) \
            * self.cfg.dtype.itemsize

    def reference_logits(self, params, ids):
        """The reference's logits, with (module comment above) the served
        token made the row's choice where the reference's own routing is a
        near-tie, and the three comparisons on identical inputs made on the
        way: where one fails, no token of the logits returned is within the
        driver's margin, so the run is not ``correct``."""
        from benchmark.harness import note

        held = Precision(params, self.cfg)
        ids = np.asarray(ids)
        out = reference_logits(params, ids, self.cfg, watch=held.watch,
                               sliced=held.sliced)
        # what the serve driver's fixed margin is worth here (PERF.md)
        note(event="reference_logits", shape=list(out.shape),
             std_over_vocab=float(out[0].std(axis=-1).mean()),
             std=float(out[0].std()))
        ties = held.ties(ids.shape)
        note(event="precision", router_limit=ROUTER_LIMIT,
             latent_limit=LATENT_LIMIT, attention_limit=ATTENTION_LIMIT,
             held=held.ok(), near_tie_positions=int(ties.sum()),
             positions=int(ties.size), tie_sigmas=TIE_SIGMAS,
             **held.readings())
        return exempted(out, ids, ties) if held.ok() else refused(out, ids)


def rescaled(params, key, head, bias):
    """``params`` with the output head times ``head`` and every expert
    layer's selection bias normal at ``bias`` from the seed: where the
    benchmark sets the spread of its random weights' logits and makes the
    bias tell choosing from weighting (the configuration's
    ``lm_head_init_range`` and ``router_bias_init_range``, with their
    reasons under ``assumed``)."""
    out = dict(params, lm_head=params["lm_head"] * head)
    tree = "moe" if "moe" in params else "layers"
    shape = params[tree]["router_bias"].shape
    out[tree] = dict(params[tree], router_bias=bias * jax.random.normal(
        jax.random.fold_in(key, 38), shape, jnp.float32))
    return out


class Precision(object):
    """The three comparisons of the module comment and the near-ties, fed by
    the reference's ``watch`` a layer and a sequence at a time."""

    def __init__(self, params, cfg):
        self.params, self.cfg = params, cfg
        self.router, self.latent, self.attention = [], [], []
        self.weights = (None, None)
        self.tied = {}                       # sequence -> [T] bool

    def sliced(self, layer, weights):
        """The layer's slice of the ``mla`` stack as ``published_names`` cut
        it for the reference: one copy (0.37 GB) serves both."""
        self.weights = (layer, weights)

    def watch(self, layer, sequence, seen):
        if self.weights[0] != layer:     # one layer's slices, made once
            self.weights = (layer, _stack(self.params, self.cfg, layer,
                                          "mla"))
        weights = self.weights[1]
        self.latent.append(latent_error(
            program_latent(weights, self.cfg, seen["attn_in"]),
            seen["latent"]))
        self.attention.extend(
            latent_error(got, seen["attn_out"][at])
            for got, at in program_attention(weights, self.cfg,
                                             seen["attn_in"]))
        if "router_logits" in seen:
            self.router.append(router_error(
                program_router_logits(self.params, self.cfg, layer,
                                      seen["ffn_in"]),
                seen["router_logits"]))
            tied = near_tie(
                np.asarray(seen["router_logits"]), np.asarray(_stack(
                    self.params, self.cfg, layer, _experts_tree(self.cfg),
                    ("router_bias",))["router_bias"]), self.cfg,
                LOGIT_NOISE[min(layer - self.cfg.dense_layers,
                                len(LOGIT_NOISE) - 1)])
            self.tied[sequence] = self.tied.get(sequence, False) | tied

    def ties(self, shape):
        """[B, T] bool: positions whose token is a near-tie in some layer."""
        out = np.zeros(shape, bool)
        for b, tied in self.tied.items():
            out[b] = tied
        return out

    def readings(self):
        return {"router_logit_err": max(self.router) if self.router else None,
                "latent_rel_err": max(self.latent),
                "attention_rel_err": max(self.attention)}

    def ok(self):
        r = self.readings()
        return r["latent_rel_err"] <= LATENT_LIMIT \
            and r["attention_rel_err"] <= ATTENTION_LIMIT and (
                r["router_logit_err"] is None
                or r["router_logit_err"] <= ROUTER_LIMIT)


def _experts_tree(cfg):
    return "moe" if cfg.dense_layers else "layers"


def _stack(params, cfg, layer, tree, keys=None):
    """Layer ``layer``'s slice of one of the program's stacks (of ``keys``
    alone: a slice is a copy, and an expert layer's whole is 0.8 GB)."""
    at = layer - cfg.dense_layers if tree == "moe" else layer
    return {k: v[at] for k, v in params[tree].items()
            if keys is None or k in keys}


def program_router_logits(params, cfg, layer, ffn_in):
    from deepspeed_tpu.models import decoder

    return decoder.router_logits(ffn_in, _stack(
        params, cfg, layer, _experts_tree(cfg), ("router",))["router"])


def program_latent(weights, cfg, attn_in):
    """What the PROGRAM would cache for one sequence from the reference's
    normed stream ``attn_in`` [T, C] (cast to the compute type, as the
    program's own norm hands it on), ``weights`` the layer's slice of the
    ``mla`` stack: [T, rank + rope] float32."""
    from deepspeed_tpu.models import decoder

    t = attn_in.shape[0]
    rope = decoder.rope_angles(jnp.arange(t)[None], cfg.qk_rope_dim,
                               cfg.rope_theta, cfg.rope_yarn)
    got = decoder.latent_token(weights, cfg,
                               attn_in[None].astype(cfg.dtype), rope)
    return got[0, 0, :, :cfg.kv_lora_rank + cfg.qk_rope_dim].astype(
        jnp.float32)


@functools.lru_cache(maxsize=None)
def _mix(cfg, name):
    """``decoder.latent_mix`` of one layer through the program's own
    ``CacheAttention`` on a paged pool of that one layer, as one program:
    (weights, h [B, S, C], the pool's plane, its table, the rows'
    frontiers) -> (y [B, S, C] float32, the plane written)."""
    from deepspeed_tpu.models import decoder, generation

    def run(weights, h, plane, tbl, pos):
        attend = generation.CacheAttention(
            cfg, {"k": plane, "pos": pos, "block_tbl": tbl}, h.shape[1], name)
        rope = decoder.rope_angles(attend.q_pos, cfg.qk_rope_dim,
                                   cfg.rope_theta, cfg.rope_yarn)
        y, (plane,) = decoder.latent_mix(weights, cfg, h, 0, rope, attend,
                                         attend.planes)
        return y.astype(jnp.float32), plane

    return jax.jit(run, donate_argnums=(2,))


def program_attention(weights, cfg, attn_in):
    """What the PROGRAM's latent attention adds to the stream for one
    sequence, from the reference's normed stream ``attn_in`` [T, C] (cast to
    the compute type, as the program's own norm hands it on; ``weights`` the
    layer's slice of the ``mla`` stack), through a paged
    latent pool as the engine holds one (page 0 the trash page, a table a
    row): [(y [n, C] float32, the positions it stands for)], first the whole
    sequence as the LANE serves a prompt, a slice of ``PROBE_PAGE`` tokens at
    a time each onto the pages the slices before it wrote, then ONE DECODE
    STEP of one row a page, row j at a position of its own in page j with
    pages 0..j as its context (the rows share the sequence's pages, and each
    writes, again, its own token into a page no other row writes)."""
    from deepspeed_tpu.models import decoder

    cfg = decoder.served_config(cfg)
    t = attn_in.shape[0]
    n_lp = -(-t // PROBE_PAGE)
    h = jnp.pad(attn_in, ((0, n_lp * PROBE_PAGE - t), (0, 0))).astype(
        cfg.dtype)
    plane = jnp.zeros((1, n_lp + 1, 1, PROBE_PAGE, cfg.latent_width),
                      cfg.dtype)
    tbl = 1 + jnp.arange(n_lp, dtype=jnp.int32)[None]
    lane, step = _mix(cfg, "prefill_attn"), _mix(cfg, None)
    out = []
    for j in range(n_lp):
        y, plane = lane(weights, h[None, j * PROBE_PAGE:(j + 1) * PROBE_PAGE],
                        plane, tbl, jnp.asarray([j * PROBE_PAGE], jnp.int32))
        out.append(y[0])
    rows = np.minimum(np.arange(n_lp) * PROBE_PAGE
                      + (37 * np.arange(n_lp) + 11) % PROBE_PAGE, t - 1)
    y, plane = step(weights, h[rows][:, None], plane,
                    jnp.tile(tbl, (n_lp, 1)), jnp.asarray(rows, jnp.int32))
    return [(jnp.concatenate(out)[:t], np.arange(t)), (y[:, 0], rows)]


def router_error(got, want):
    return float(jnp.max(jnp.abs(got - want)))


def latent_error(got, want):
    return float(jnp.max(jnp.linalg.norm(got - want, axis=-1)
                         / jnp.linalg.norm(want, axis=-1)))


def tie_distance(logits, bias, cfg, noise):
    """[T] float, from the REFERENCE's router logits [T, E] (float32) and the
    selection bias alone: how far, in standard deviations of a logit's noise
    ``noise``, a token stands from keeping ANOTHER SET OF HELD EXPERTS. A
    score is ``sigmoid(logit) + bias``, so a logit's noise moves it by the
    sigmoid's slope ``s (1 - s)``, and the gap between two scores is
    measured against the root of their two slopes' squares. The nearest of:
    a held expert against the edge of the choice (kept: the best score left
    out; left out of a kept group: the last score kept); a kept and a cut
    group changing sides (a group's score is the sum of its two largest
    scores, four slopes; the last two kept against the first two cut) where
    that keeps another set of held experts, or as far again as a held expert
    then stands from the edge. A tie among experts and groups held
    elsewhere that leaves the held ones where they are counts for nothing:
    it moves this chip's sum by the renormalisation alone. Module comment,
    NEAR-TIE."""
    t, e = logits.shape
    first, count = cfg.held
    k, ng, tg = cfg.experts_per_token, cfg.n_group, cfg.topk_group
    size = e // ng
    rows = np.arange(t)
    score = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    slope, choose = score * (1.0 - score), score + bias[None]

    def held_choice(kept):
        """(which held experts [T, count] are kept, the nearest held
        expert's distance from the edge [T]) with the groups ``kept``."""
        kept = np.repeat(kept, size, axis=-1)
        masked = np.where(kept, choose, 0.0)
        order = np.argsort(masked, -1)
        last_in, first_out = order[:, e - k], order[:, e - k - 1]
        held = slice(first, first + count)
        inside = kept[:, held] & (
            masked[:, held] >= masked[rows, last_in, None])
        edge = np.where(inside, first_out[:, None], last_in[:, None])
        far = np.abs(masked[:, held] - masked[rows[:, None], edge]) / (
            noise * np.sqrt(slope[:, held] ** 2
                            + slope[rows[:, None], edge] ** 2))
        return inside, np.where(kept[:, held], far, np.inf).min(-1)

    if tg >= ng:
        return held_choice(np.ones((t, ng), bool))[1]
    grouped = choose.reshape(t, ng, size)
    two = np.argsort(grouped, -1)[..., -2:]
    group = np.take_along_axis(grouped, two, -1).sum(-1)
    spread = (np.take_along_axis(slope.reshape(t, ng, size), two, -1)
              ** 2).sum(-1)
    rank = np.argsort(-group, -1)                      # best group first
    kept = np.zeros((t, ng), bool)
    kept[rows[:, None], rank[:, :tg]] = True
    inside, out = held_choice(kept)
    for a in range(max(tg - 2, 0), tg):                # a kept group ...
        for b in range(tg, min(tg + 2, ng)):           # ... and a cut one
            ga, gb = rank[:, a], rank[:, b]
            swap = (group[rows, ga] - group[rows, gb]) / (
                noise * np.sqrt(spread[rows, ga] + spread[rows, gb]))
            other = kept.copy()
            other[rows, ga], other[rows, gb] = False, True
            swapped, far = held_choice(other)
            out = np.minimum(out, np.where(
                (swapped != inside).any(-1), swap, np.maximum(swap, far)))
    return out


def near_tie(logits, bias, cfg, noise, sigmas=None):
    """[T] bool: ``tie_distance`` under ``sigmas`` (``TIE_SIGMAS``)."""
    return tie_distance(logits, bias, cfg, noise) < (
        TIE_SIGMAS if sigmas is None else sigmas)


def exempted(out, ids, ties):
    """``out`` [B, T, V] with, at every position ``ties`` [B, T] marks, the
    token the stream holds next given the row's largest logit: that
    position's margin reads 0."""
    nxt = np.roll(ids, -1, axis=1)
    b, t = np.nonzero(ties)
    out[b, t, nxt[b, t]] = out[b, t].max(-1)
    return out


def refused(out, ids):
    """``out`` [B, T, V] with ``REFUSED`` added, at every position, to a
    token that is NOT the one the stream holds next: the margin of every
    served token then reads at least about ``REFUSED``."""
    nxt = np.roll(ids, -1, axis=1)
    b, t = np.indices(ids.shape)
    out[b, t, (nxt + 1) % out.shape[-1]] += REFUSED
    return out


class Experts(object):
    """One layer's routed experts under a published name: ``self[e]`` is
    expert ``e``'s matrix, sliced out of the program's stack when asked."""

    def __init__(self, stack, layer, columns):
        self.stack, self.layer, self.columns = stack, layer, columns

    def __getitem__(self, e):
        return self.stack[self.layer, e][:, self.columns]


class Window(object):
    """Columns ``lo .. lo + width - 1`` of one layer of a stack of matrices
    under a published name: ``self[rows, columns]`` is cut out of the stack
    in one slice when the reference asks for a block (the whole is 0.26 GB a
    dense matrix, a copy a slice)."""

    def __init__(self, stack, layer, lo=0, width=None):
        self.stack, self.layer, self.lo = stack, layer, lo
        self.shape = (stack.shape[1],
                      stack.shape[2] - lo if width is None else width)

    def __getitem__(self, index):
        rows, columns = index if isinstance(index, tuple) \
            else (index, slice(None))
        start, stop, step = columns.indices(self.shape[1])
        return self.stack[self.layer, rows,
                          self.lo + start:self.lo + stop:step]


def interleaved(width):
    """Where the program's rotary columns (HALVES order: the checkpoint's
    lane 2j at j, lane 2j + 1 at width / 2 + j) go back to as published:
    ``published = program[:, interleaved(width)]``."""
    half = width // 2
    return np.stack([np.arange(half), half + np.arange(half)], 1).reshape(-1)


def published_names(params, cfg, sliced=None):
    """The program's tree under the reference's (the published) names:
    ``wq_nope`` / ``wq_rope`` and ``w_uk`` / ``w_uv`` put back together as
    ``q_b_proj`` and ``kv_b_proj``, the rotary columns back in the
    checkpoint's interleaved order. ``layers`` is a
    generator: one layer's slices exist at a time, and of its routed experts
    one expert's (``Experts``); ``sliced(layer, slices)`` is shown a layer's
    slice of the ``mla`` stack when it is cut."""
    nh, r, dn, dr, dv = cfg.n_head, cfg.kv_lora_rank, cfg.qk_nope_dim, \
        cfg.qk_rope_dim, cfg.v_head_dim
    f, fs, fd = cfg.expert_width, cfg.shared_width, cfg.dense_width
    pairs = interleaved(dr)
    kv_cols = np.concatenate([np.arange(r), r + pairs])

    def layers():
        for i in range(cfg.n_layer):
            a = _stack(params, cfg, i, "mla")
            if sliced is not None:
                sliced(i, a)
            norms = _stack(params, cfg, i, "layers",
                           ("attn_norm", "ffn_norm"))
            out = {"input_layernorm": norms["attn_norm"],
                   "post_attention_layernorm": norms["ffn_norm"],
                   "q_a_proj": a["wq_a"], "q_a_layernorm": a["q_a_norm"],
                   "q_b_proj": jnp.concatenate(
                       [a["wq_nope"].T.reshape(-1, nh, dn),
                        a["wq_rope"].T.reshape(-1, nh, dr)[..., pairs]],
                       axis=-1).reshape(-1, nh * (dn + dr)),
                   "kv_a_proj_with_mqa": a["wkv_a"][:, kv_cols],
                   "kv_a_layernorm": a["kv_a_norm"],
                   "kv_b_proj": jnp.concatenate(
                       [a["w_uk"].transpose(1, 0, 2), a["w_uv"].transpose(
                           2, 0, 1)], axis=-1).reshape(r, nh * (dn + dv)),
                   "o_proj": a["wo"]}
            if i < cfg.dense_layers:
                d = params["dense"]
                out.update(gate_proj=Window(d["w_gate_up"], i, 0, fd),
                           up_proj=Window(d["w_gate_up"], i, fd, fd),
                           down_proj=Window(d["w_down"], i))
            else:
                tree = "moe" if cfg.dense_layers else "layers"
                at = i - cfg.dense_layers
                stacks = params[tree]
                out.update(
                    gate=stacks["router"][at],
                    e_score_correction_bias=stacks["router_bias"][at],
                    gate_proj=Experts(stacks["w_gate_up"], at, slice(0, f)),
                    up_proj=Experts(stacks["w_gate_up"], at, slice(f, 2 * f)),
                    down_proj=Experts(stacks["w_down"], at, slice(None)),
                    shared_gate=stacks["shared_gate_up"][at][:, :fs],
                    shared_up=stacks["shared_gate_up"][at][:, fs:],
                    shared_down=stacks["shared_down"][at])
            yield out

    return {"embed_tokens": params["embed"], "layers": layers(),
            "norm": params["final_norm"], "lm_head": params["lm_head"]}


def hyper(cfg):
    """What the reference is told beside the weights."""
    yarn = cfg.rope_yarn
    return {"n_head": cfg.n_head, "qk_nope": cfg.qk_nope_dim,
            "qk_rope": cfg.qk_rope_dim, "v_head": cfg.v_head_dim,
            "kv_lora_rank": cfg.kv_lora_rank, "theta": cfg.rope_theta,
            "yarn": None if yarn is None else {
                "factor": yarn[0],
                "original_max_position_embeddings": yarn[1],
                "beta_fast": yarn[2], "beta_slow": yarn[3],
                "mscale": yarn[4], "mscale_all_dim": yarn[5]},
            "eps": cfg.rms_norm_eps, "top_k": cfg.experts_per_token,
            "n_group": cfg.n_group, "topk_group": cfg.topk_group,
            "norm_topk_prob": cfg.norm_topk_prob,
            "routed_scaling_factor": cfg.routed_scaling, "held": cfg.held}


def reference_logits(params, ids, cfg, watch=None, sliced=None):
    """The plain reference on the program's parameter tree, for a
    ``DecoderConfig`` ``cfg`` (the tests call it at a tiny size)."""
    return reference.logits(published_names(params, cfg, sliced), ids,
                            hyper(cfg), watch=watch)
