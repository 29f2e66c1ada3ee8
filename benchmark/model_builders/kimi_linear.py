"""Configurations of ``"model_type": "kimi_linear"`` (Kimi-Linear-48B-A3B): the
program's config-driven decoder block (``deepspeed_tpu/models/decoder.py``)
with Kimi Delta Attention layers (``models/kda.py``: a matrix state a slot)
beside latent attention without positions over a one-plane cache as deep as
the MLA layers only, a leading dense layer and the sigmoid router over a
chip's share of the experts, built from the published keys and the share the
file states; its weights from the seed, its plain reference and its account
of the cache. Serving only: it owes what the ``serve`` driver asks and
nothing of training (benchmark/README.md, "What a builder owes").
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness
from benchmark.reference import kimi_linear as reference

# The near-tie rule, the selection bias and the head's scale are DeepSeek-V3's
# builder's, whose router this one is with one group: its functions, not
# copies of them.
shared = harness.load_by_name("model_builders", "deepseek_v3")

# WHAT HOLDS THE PRECISION THE CONFIGURATION STATES (its ``assumed``: a float32
# KDA state, bf16 convolution tails, a float32 router from the float32 norm,
# a bf16 latent a token). The serve driver's one limit, the token margin, is
# held at the logit spread of the other serving cells (0.645); it sees a wrong
# token, a wrong stream or a layer gone astray, but not a state rounded to
# bf16 nor a cache rounded to 8 bits: those move the logits by less than the
# program's bf16 activations do (Granite's builder, PERF.md PR 33). So five
# quantities are held on IDENTICAL inputs, at the cell's widths, on the
# checked sequences: the program's own functions are handed what the
# reference computed and must return what the reference returns. Each limit
# lies between what the sound program reads and what reads when the quantity
# is computed in the precision below (PERF.md, PR 42, has both readings):
#   state: the largest relative error (Frobenius, a head) of a KDA layer's
#     state, the program's recurrence on the reference's q, k, v, g, beta,
#     carried in the type ``kda.state_shapes`` gives the pool: after THE LANE
#     (``kda.chunked`` over the first ``LANE`` tokens, against the reference's
#     token loop over the same) and after THE SCAN (``kda.step`` a token at a
#     time over the rest, against the reference's state after the last
#     token). Granite's limit for the same quantity; below: the state
#     carried in bf16.
#   tail: the largest relative error (Euclidean, a row) of the three rows a
#     slot keeps of a KDA layer's q | k | v BEFORE the convolutions
#     (``qkv @ wqkv`` of the reference's normed stream through
#     ``mamba2.causal_conv``, which cuts the tail), against the reference's
#     float32 rows; below: the tail rounded to ``float8_e4m3fn``.
#   router, latent, attention: DeepSeek-V3's builder's three (the router's
#     logits; what ``decoder.latent_token`` would cache; what
#     ``decoder.latent_mix`` adds to the stream through the program's own
#     ``CacheAttention`` on a PAGED LATENT POOL, lane slices then one decode
#     step of a row a page: ``kv_append`` and ``latent_decode`` on the chip),
#     here without positions and with queries straight from the stream.
STATE_LIMIT = 2e-3
TAIL_LIMIT = 1e-2
ROUTER_LIMIT = shared.ROUTER_LIMIT
LATENT_LIMIT = shared.LATENT_LIMIT
ATTENTION_LIMIT = shared.ATTENTION_LIMIT
PROBE_PAGE = shared.PROBE_PAGE
# the lane's slice (the cell's ``prefill_chunk``): a prompt is one of them
LANE = 128

# WHERE THE REFERENCE'S OWN CHOICE IS A NEAR-TIE, EITHER CHOICE IS THE MODEL'S
# (``model_builders/deepseek_v3.py``, the same rule). The rms of a router
# logit's bf16 noise a layer, the program beside its own float32 self
# (matmuls at ``highest``) on the same weights and 4 x 1,024 tokens (my chip
# run, PERF.md PR 42: 0.0082 in the first expert layer to 0.0323 in the
# eleventh, logits that spread 0.95): expert layer 1, 2, ..
LOGIT_NOISE = (0.0083, 0.0118, 0.0139, 0.0171, 0.0195, 0.0219, 0.0238,
               0.0262, 0.0285, 0.0308, 0.0324)
TIE_SIGMAS = shared.TIE_SIGMAS


class Model(object):
    def __init__(self, config):
        from deepspeed_tpu.models.decoder import DecoderConfig, DecoderLM

        for key, published in (
                ("hidden_act", "silu"), ("mla_use_nope", True),
                ("moe_layer_freq", 1), ("moe_renormalize", True),
                ("moe_router_activation_func", "sigmoid"),
                ("num_expert_group", 1), ("topk_group", 1),
                ("q_lora_rank", None), ("rope_scaling", None),
                ("num_shared_experts", 1), ("tie_word_embeddings", False),
                ("num_nextn_predict_layers", 0)):
            if config[key] != published:
                raise ValueError("model_builders/kimi_linear.py builds "
                                 "{}={!r} only".format(key, published))
        if config["num_key_value_heads"] != config["num_attention_heads"]:
            raise ValueError("latent attention gives every query head a "
                             "key and a value of its own")
        linear = config["linear_attn_config"]
        n_layer = config["num_hidden_layers"]
        if sorted(linear["kda_layers"] + linear["full_attn_layers"]) != \
                list(range(1, n_layer + 1)):
            raise ValueError("kda_layers and full_attn_layers name each of "
                             "the {} layers once (1-indexed)".format(n_layer))
        kinds = tuple("kda" if i + 1 in linear["kda_layers"] else "attention"
                      for i in range(n_layer))
        first, held = config.get("experts_held", (0, config["num_experts"]))
        published = config.get("router_outputs", config["num_experts"])
        if held != config["num_experts"] or first + held > published:
            raise ValueError("num_experts counts the experts held")
        n_head = config["num_attention_heads"]
        self.cfg = DecoderConfig(
            vocab_size=config["vocab_size"], n_layer=n_layer, n_head=n_head,
            head_dim=config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
            hidden_size=config["hidden_size"],
            n_positions=config["model_max_length"], n_experts=published,
            experts_per_token=config["num_experts_per_token"],
            expert_width=config["moe_intermediate_size"],
            rms_norm_eps=config["rms_norm_eps"], qk_norm=False,
            norm_topk_prob=config["moe_renormalize"],
            tie_word_embeddings=False,
            dtype=jnp.dtype(config["deployment"]["compute_dtype"]),
            initializer_range=config["initializer_range"], rope=False,
            shared_width=config["num_shared_experts"]
            * config["moe_intermediate_size"],
            experts_held=None if held == published else (first, held),
            layer_types=kinds, kv_lora_rank=config["kv_lora_rank"],
            q_lora_rank=0, qk_nope_dim=config["qk_nope_head_dim"],
            qk_rope_dim=config["qk_rope_head_dim"],
            v_head_dim=config["v_head_dim"],
            dense_layers=config["first_k_dense_replace"],
            dense_width=config["intermediate_size"],
            router_scoring="sigmoid", n_group=1, topk_group=1,
            routed_scaling=float(config["routed_scaling_factor"]),
            kda_heads=linear["num_heads"], kda_head_dim=linear["head_dim"],
            kda_conv=linear["short_conv_kernel_size"])
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
        from deepspeed_tpu.inference.kv_pool import slot_state_nbytes
        from deepspeed_tpu.models.decoder import cache_spec

        c = self.cfg
        w, d = c.kda_heads * c.kda_head_dim, c.kda_head_dim
        kda = (3 * c.hidden_size * w + 3 * w * c.kda_conv
               + 2 * (c.hidden_size * d + d * w) + w
               + c.hidden_size * c.kda_heads + c.kda_heads + d
               + w * c.hidden_size)
        attention = (c.hidden_size * c.n_head * c.head_dim
                     + c.hidden_size * (c.kv_lora_rank + c.qk_rope_dim)
                     + c.kv_lora_rank + c.kv_lora_rank * c.n_head
                     * (c.qk_nope_dim + c.v_head_dim)
                     + c.n_head * c.v_head_dim * c.hidden_size)
        dense = 3 * c.hidden_size * c.dense_width
        experts = (c.hidden_size * c.n_experts + c.n_experts
                   + c.held[1] * 3 * c.hidden_size * c.expert_width
                   + 3 * c.hidden_size * c.shared_width)
        spec = cache_spec(c)
        return {"num_hidden_layers": c.n_layer, "hidden_size": c.hidden_size,
                "layer_types": list(c.kinds), "heads": c.n_head,
                "kda": [c.kda_heads, c.kda_head_dim, c.kda_conv],
                "qk_nope_head_dim": c.qk_nope_dim,
                "qk_rope_head_dim": c.qk_rope_dim,
                "v_head_dim": c.v_head_dim, "kv_lora_rank": c.kv_lora_rank,
                "first_k_dense_replace": c.dense_layers,
                "intermediate_size": c.dense_width,
                "router_outputs": c.n_experts, "experts_held": list(c.held),
                "num_experts_per_token": c.experts_per_token,
                "moe_intermediate_size": c.expert_width,
                "vocab_size": c.vocab_size,
                "softmax_scale": c.softmax_scale,
                "latent_stored_width": c.latent_width,
                "latent_layers": spec.n_layer,
                "state_bytes_per_slot": slot_state_nbytes(spec),
                "params": len(c.kda_layers) * kda
                + len(c.kv_layers) * attention + c.dense_layers * dense
                + (c.n_layer - c.dense_layers) * experts
                + 2 * c.n_layer * c.hidden_size
                + 2 * c.vocab_size * c.hidden_size + c.hidden_size}

    def init_params(self, seed, on_host=False):
        """Random weights from the seed in the type they are served in, made
        in one jitted program on the default device. The seed is an argument
        of that program, so that one cached program serves every seed."""
        return jax.jit(lambda key: shared.rescaled(
            self.module.init(key)["params"], key, self.head_range
            / self.cfg.initializer_range, self.bias_range))(
            jax.random.PRNGKey(seed))

    def kv_bytes_per_token_layer(self):
        """What MUST be read of a cached token in one layer THAT CACHES
        (3 of the 12 here): the compressed latent and the one shared key,
        in the type the engine stores."""
        return (self.cfg.kv_lora_rank + self.cfg.qk_rope_dim) \
            * self.cfg.dtype.itemsize

    def reference_logits(self, params, ids):
        """The reference's logits, with (module comment above) the served
        token made the row's choice where the reference's own routing is a
        near-tie, and the comparisons on identical inputs made on the way:
        where one fails, no token of the logits returned is within the
        driver's margin, so the run is not ``correct``."""
        held = Precision(params, self.cfg)
        ids = np.asarray(ids)
        out = reference_logits(params, ids, self.cfg, watch=held.watch)
        # what the serve driver's fixed margin is worth here (PERF.md)
        harness.note(event="reference_logits", shape=list(out.shape),
                     std_over_vocab=float(out[0].std(axis=-1).mean()),
                     std=float(out[0].std()))
        ties = held.ties(ids.shape)
        harness.note(
            event="precision", state_limit=STATE_LIMIT, tail_limit=TAIL_LIMIT,
            router_limit=ROUTER_LIMIT, latent_limit=LATENT_LIMIT,
            attention_limit=ATTENTION_LIMIT, held=held.ok(),
            near_tie_positions=int(ties.sum()), positions=int(ties.size),
            tie_sigmas=TIE_SIGMAS, **held.readings())
        return shared.exempted(out, ids, ties) if held.ok() \
            else shared.refused(out, ids)


class Precision(object):
    """The comparisons of the module comment and the near-ties, fed by the
    reference's ``watch`` a layer and a sequence at a time."""

    LIMITS = (("state_lane_rel_err", STATE_LIMIT),
              ("state_rel_err", STATE_LIMIT), ("tail_rel_err", TAIL_LIMIT),
              ("router_logit_err", ROUTER_LIMIT),
              ("latent_rel_err", LATENT_LIMIT),
              ("attention_rel_err", ATTENTION_LIMIT))

    def __init__(self, params, cfg):
        self.params, self.cfg = params, cfg
        self.seen = {name: [] for name, _ in self.LIMITS}
        self.tied = {}                       # sequence -> [T] bool

    def watch(self, layer, sequence, seen):
        cfg = self.cfg
        if cfg.kinds[layer] == "kda":
            j = cfg.kda_layers.index(layer)
            lane, last = state_errors(cfg, seen)
            self.seen["state_lane_rel_err"].append(lane)
            self.seen["state_rel_err"].append(last)
            self.seen["tail_rel_err"].append(shared.latent_error(
                program_tail(self.params["kda"]["wqkv"][j], cfg,
                             seen["mix_in"]), seen["tail"]))
        else:
            weights = {k: v[cfg.kv_layers.index(layer)]
                       for k, v in self.params["mla"].items()}
            self.seen["latent_rel_err"].append(shared.latent_error(
                program_latent(weights, cfg, seen["mix_in"]),
                seen["latent"]))
            self.seen["attention_rel_err"].extend(
                shared.latent_error(got, seen["mix_out"][at])
                for got, at in program_attention(weights, cfg,
                                                 seen["mix_in"]))
        if "router_logits" in seen:
            from deepspeed_tpu.models import decoder

            at = layer - cfg.dense_layers
            self.seen["router_logit_err"].append(shared.router_error(
                decoder.router_logits(seen["ffn_in"],
                                      self.params["moe"]["router"][at]),
                seen["router_logits"]))
            tied = shared.near_tie(
                np.asarray(seen["router_logits"]),
                np.asarray(self.params["moe"]["router_bias"][at]), cfg,
                LOGIT_NOISE[min(at, len(LOGIT_NOISE) - 1)], TIE_SIGMAS)
            self.tied[sequence] = self.tied.get(sequence, False) | tied

    def ties(self, shape):
        """[B, T] bool: positions whose token is a near-tie in some layer."""
        out = np.zeros(shape, bool)
        for b, tied in self.tied.items():
            out[b] = tied
        return out

    def readings(self):
        return {name: max(v) if v else None for name, v in self.seen.items()}

    def ok(self):
        r = self.readings()
        return all(r[name] is None or r[name] <= limit
                   for name, limit in self.LIMITS)


def state_errors(cfg, seen, dtype=None):
    """(after the lane, after the scan): the largest relative error a head
    of the PROGRAM's recurrence on the reference's inputs of one KDA layer
    and sequence (module comment); ``dtype``: the type the state is carried
    in, the pool's own unless given."""
    from deepspeed_tpu.models import kda

    if dtype is None:
        (_, _, dtype), = [s for s in kda.state_shapes(cfg)
                          if s[0] == kda.state_key(0)]
    inputs = tuple(seen[k] for k in ("q", "k", "v", "g", "beta"))
    lane = min(LANE, inputs[0].shape[0] // 2)
    got_lane, got = _recurrence(*inputs, dtype=jnp.dtype(dtype), lane=lane)
    want_lane = _reference_state(*(x[:lane] for x in inputs))

    def err(got, want):
        return float(jnp.max(jnp.sqrt(
            jnp.sum(jnp.square(got - want), axis=(1, 2))
            / jnp.sum(jnp.square(want), axis=(1, 2)))))

    return err(got_lane, want_lane), err(got, seen["state"])


@jax.jit
def _reference_state(q, k, v, g, beta):
    with jax.default_matmul_precision("highest"):
        return reference.delta_rule(q, k, v, g, beta)[1]


@functools.partial(jax.jit, static_argnames=("dtype", "lane"))
def _recurrence(q, k, v, g, beta, dtype, lane):
    """The state [H, d, d] float32 of one sequence (a batch of 1) after its
    first ``lane`` tokens through the chunked form, and after the rest a
    token at a time, carried as ``kda.mixer`` carries it: computed in
    float32, kept in the pool's type."""
    from deepspeed_tpu.models import kda

    q, k, v, g, beta = (x[None] for x in (q, k, v, g, beta))
    h, d = q.shape[2:]
    _, state = kda.chunked(q[:, :lane], k[:, :lane], v[:, :lane],
                           g[:, :lane], beta[:, :lane],
                           jnp.zeros((1, h, d, d), jnp.float32))
    after_lane = state.astype(dtype)

    def token(state, x):
        _, state32 = kda.step(*x, state.astype(jnp.float32))
        return state32.astype(dtype), None

    state, _ = jax.lax.scan(token, after_lane, tuple(
        jnp.moveaxis(x[:, lane:], 1, 0) for x in (q, k, v, g, beta)))
    return after_lane[0].astype(jnp.float32), state[0].astype(jnp.float32)


def program_tail(wqkv, cfg, mix_in, dtype=None):
    """The rows a slot would keep of one KDA layer's q | k | v before the
    convolutions, from the reference's normed stream ``mix_in`` [T, C] (cast
    to the compute type, as the program's own norm hands it on), the whole
    sequence one slice from an empty tail: [K - 1, 3 H d] float32.
    ``dtype``: the type the tail is kept in, the pool's own unless given."""
    from deepspeed_tpu.models import kda, mamba2

    (_, shape, tail_dtype), = [s for s in kda.state_shapes(cfg)
                               if s[0] == kda.conv_key(0)]
    t = mix_in.shape[0]
    _, tail = mamba2.causal_conv(
        mix_in[None].astype(cfg.dtype) @ wqkv.astype(cfg.dtype),
        jnp.zeros((1,) + tuple(shape), dtype or tail_dtype),
        jnp.zeros((cfg.kda_conv, shape[1]), cfg.dtype),
        jnp.zeros((), jnp.float32), jnp.asarray([t], jnp.int32))
    return tail[0].astype(jnp.float32)


def program_latent(weights, cfg, mix_in):
    """What the PROGRAM would cache for one sequence from the reference's
    normed stream ``mix_in`` [T, C], ``weights`` the layer's slice of the
    ``mla`` stack: [T, rank + shared lanes] float32."""
    from deepspeed_tpu.models import decoder

    got = decoder.latent_token(weights, cfg,
                               mix_in[None].astype(cfg.dtype), None)
    return got[0, 0, :, :cfg.kv_lora_rank + cfg.qk_rope_dim].astype(
        jnp.float32)


@functools.lru_cache(maxsize=None)
def _mix(cfg, name):
    """``decoder.latent_mix`` of one layer through the program's own
    ``CacheAttention`` on a paged pool of that one layer, as one program
    (``model_builders/deepseek_v3.py`` ``_mix``, without positions)."""
    from deepspeed_tpu.models import decoder, generation

    def run(weights, h, plane, tbl, pos):
        attend = generation.CacheAttention(
            cfg, {"k": plane, "pos": pos, "block_tbl": tbl}, h.shape[1], name)
        y, (plane,) = decoder.latent_mix(weights, cfg, h, 0, None, attend,
                                         attend.planes)
        return y.astype(jnp.float32), plane

    return jax.jit(run, donate_argnums=(2,))


def program_attention(weights, cfg, mix_in):
    """What the PROGRAM's latent attention adds to the stream for one
    sequence through a paged latent pool as the engine holds one: first the
    whole sequence as the LANE serves a prompt, a slice of ``PROBE_PAGE``
    tokens at a time, then ONE DECODE STEP of one row a page
    (``model_builders/deepseek_v3.py`` ``program_attention``, whose walk
    this is): [(y [n, C] float32, the positions it stands for)]."""
    from deepspeed_tpu.models import decoder

    cfg = decoder.served_config(cfg)
    t = mix_in.shape[0]
    n_lp = -(-t // PROBE_PAGE)
    h = jnp.pad(mix_in, ((0, n_lp * PROBE_PAGE - t), (0, 0))).astype(
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


def published_names(params, cfg):
    """The program's tree under the reference's (the published) names:
    ``wqkv``, ``conv_w`` and ``w_low`` cut into the projections they hold,
    ``wq_nope`` / ``wq_rope`` and ``w_uk`` / ``w_uv`` put back together as
    ``q_proj`` and ``kv_b_proj``. ``layers`` is a generator: one layer's
    slices exist at a time, and of its routed experts one expert's
    (``Experts``)."""
    nh, r, dn, dr, dv = cfg.n_head, cfg.kv_lora_rank, cfg.qk_nope_dim, \
        cfg.qk_rope_dim, cfg.v_head_dim
    f, fs, fd = cfg.expert_width, cfg.shared_width, cfg.dense_width
    d, w = cfg.kda_head_dim, cfg.kda_heads * cfg.kda_head_dim

    def layers():
        for i, kind in enumerate(cfg.kinds):
            out = {"input_layernorm": params["layers"]["attn_norm"][i],
                   "post_attention_layernorm": params["layers"]["ffn_norm"][i]}
            if kind == "kda":
                a = {k: v[cfg.kda_layers.index(i)]
                     for k, v in params["kda"].items()}
                for n, name in enumerate("qkv"):
                    cols = slice(n * w, (n + 1) * w)
                    out[name + "_proj"] = a["wqkv"][:, cols]
                    out[name + "_conv"] = a["conv_w"][:, cols]
                out.update(f_a_proj=a["w_low"][:, :d],
                           g_a_proj=a["w_low"][:, d:2 * d],
                           b_proj=a["w_low"][:, 2 * d:],
                           f_b_proj=a["w_fb"], g_b_proj=a["w_gb"],
                           dt_bias=a["dt_bias"], A_log=a["A_log"],
                           o_norm=a["norm"], o_proj=a["wo"])
            else:
                a = {k: v[cfg.kv_layers.index(i)]
                     for k, v in params["mla"].items()}
                out.update(
                    q_proj=jnp.concatenate(
                        [a["wq_nope"].T.reshape(-1, nh, dn),
                         a["wq_rope"].T.reshape(-1, nh, dr)],
                        axis=-1).reshape(-1, nh * (dn + dr)),
                    kv_a_proj_with_mqa=a["wkv_a"],
                    kv_a_layernorm=a["kv_a_norm"],
                    kv_b_proj=jnp.concatenate(
                        [a["w_uk"].transpose(1, 0, 2), a["w_uv"].transpose(
                            2, 0, 1)], axis=-1).reshape(r, nh * (dn + dv)),
                    o_proj=a["wo"])
            if i < cfg.dense_layers:
                dense = params["dense"]
                out.update(
                    gate_proj=shared.Window(dense["w_gate_up"], i, 0, fd),
                    up_proj=shared.Window(dense["w_gate_up"], i, fd, fd),
                    down_proj=shared.Window(dense["w_down"], i))
            else:
                at, stacks = i - cfg.dense_layers, params["moe"]
                out.update(
                    gate=stacks["router"][at],
                    e_score_correction_bias=stacks["router_bias"][at],
                    gate_proj=shared.Experts(stacks["w_gate_up"], at,
                                             slice(0, f)),
                    up_proj=shared.Experts(stacks["w_gate_up"], at,
                                           slice(f, 2 * f)),
                    down_proj=shared.Experts(stacks["w_down"], at,
                                             slice(None)),
                    shared_gate=stacks["shared_gate_up"][at][:, :fs],
                    shared_up=stacks["shared_gate_up"][at][:, fs:],
                    shared_down=stacks["shared_down"][at])
            yield out

    return {"embed_tokens": params["embed"], "layers": layers(),
            "norm": params["final_norm"], "lm_head": params["lm_head"]}


def hyper(cfg):
    """What the reference is told beside the weights."""
    return {"layer_types": cfg.kinds, "kda_heads": cfg.kda_heads,
            "kda_head_dim": cfg.kda_head_dim, "n_head": cfg.n_head,
            "qk_nope": cfg.qk_nope_dim, "qk_rope": cfg.qk_rope_dim,
            "v_head": cfg.v_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
            "eps": cfg.rms_norm_eps, "top_k": cfg.experts_per_token,
            "renormalize": cfg.norm_topk_prob,
            "routed_scaling_factor": cfg.routed_scaling, "held": cfg.held}


def reference_logits(params, ids, cfg, watch=None):
    """The plain reference on the program's parameter tree, for a
    ``DecoderConfig`` ``cfg`` (the tests call it at a tiny size)."""
    return reference.logits(published_names(params, cfg), ids, hyper(cfg),
                            watch=watch)
