"""Configurations of ``"model_type": "granitemoehybrid"`` (Granite 4.0-H): the
program's config-driven decoder block (``deepspeed_tpu/models/decoder.py``)
as a hybrid stack (Mamba-2 layers with a recurrent state a slot beside
grouped-query attention layers, the chip's share of the routed experts and a
shared expert), built from the published keys and the share the file states;
its weights from the seed, its plain reference and its account of the cache.
Serving only: it owes what the ``serve`` driver asks and nothing of training
(benchmark/README.md, "What a builder owes").
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import granitemoehybrid as reference

# WHAT HOLDS THE PRECISION THE CONFIGURATION STATES (its ``assumed``: a
# float32 recurrent state, a float32 router). The serve driver's one limit,
# the token margin, cannot: the program's bf16 activations make as much noise
# in the logits as a bf16 state or router does (measured on the chip, PERF.md
# PR 33: a bf16 state changed no served token of 5,100). So the two are held
# on IDENTICAL inputs, at the cell's widths, on the checked sequences: the
# program's own functions are handed what the reference computed and must
# return what the reference returns. Each limit lies between what the sound
# program reads and what the reference reads when computed in the precision
# below (bf16) and put in the program's place; my chip runs, PERF.md PR 33:
#   state: the largest relative error (Frobenius, a head) of the state after
#     the last token, the program's recurrence (``mamba2.ssd`` over a chunk
#     and a half, then ``mamba2.step`` a token at a time, the state carried
#     in the type ``mamba2.state_shapes`` gives the pool) against the
#     reference's token-by-token scan on the reference's x, dt, B, C. Sound:
#     4.6e-6 to 1.0e-4 over nine runs (what a slow head keeps of the chunked
#     form's 384 tokens, which alone reads up to 2.5e-4 against a scan that
#     multiplies 384 rounded ``exp``); a bf16 state: 0.13 to 1.2 (its best
#     layer and sequence 0.014).
#   router: the largest difference of a router logit,
#     ``decoder.router_logits`` against the reference's on the reference's
#     normed stream. Sound: 1.9e-6 to 3.8e-6; the matmul in bf16: 0.018 to
#     0.024.
STATE_LIMIT = 2e-3
ROUTER_LIMIT = 3e-4
# What a failed comparison adds to a logit no stream served: the driver's
# margin then reads this, far from anything rounding gives.
REFUSED = 1e3


class Model(object):
    def __init__(self, config):
        from deepspeed_tpu.models.decoder import DecoderConfig, DecoderLM

        for key, published in (
                ("attention_bias", False), ("hidden_act", "silu"),
                ("mamba_conv_bias", True), ("mamba_proj_bias", False),
                ("mamba_n_groups", 1), ("normalization_function", "rmsnorm"),
                ("position_embedding_type", "nope"),
                ("tie_word_embeddings", True)):
            if config[key] != published:
                raise ValueError("model_builders/granitemoehybrid.py builds "
                                 "{}={!r} only".format(key, published))
        kinds = tuple(config["layer_types"])
        if len(kinds) != config["num_hidden_layers"] \
                or not set(kinds) <= {"mamba", "attention"}:
            raise ValueError("layer_types names a kind for each of the {} "
                             "layers".format(config["num_hidden_layers"]))
        if config["mamba_n_heads"] * config["mamba_d_head"] != \
                config["mamba_expand"] * config["hidden_size"]:
            raise ValueError("mamba_n_heads x mamba_d_head is mamba_expand "
                             "x hidden_size")
        first, held = config.get("experts_held",
                                 (0, config["num_local_experts"]))
        published = config.get("router_outputs", config["num_local_experts"])
        if held != config["num_local_experts"] or first + held > published:
            raise ValueError("num_local_experts counts the experts held")
        n_head = config["num_attention_heads"]
        self.cfg = DecoderConfig(
            vocab_size=config["vocab_size"],
            n_layer=config["num_hidden_layers"], n_head=n_head,
            head_dim=config["hidden_size"] // n_head,
            hidden_size=config["hidden_size"],
            n_positions=config["max_position_embeddings"],
            n_experts=published,
            experts_per_token=config["num_experts_per_tok"],
            expert_width=config["intermediate_size"],
            rms_norm_eps=config["rms_norm_eps"], qk_norm=False,
            # the softmax over the kept logits
            norm_topk_prob=True, tie_word_embeddings=True,
            dtype=jnp.dtype(config["deployment"]["compute_dtype"]),
            initializer_range=config["initializer_range"],
            n_kv_head=config["num_key_value_heads"], rope=False,
            attn_scale=float(config["attention_multiplier"]),
            embedding_multiplier=float(config["embedding_multiplier"]),
            residual_multiplier=float(config["residual_multiplier"]),
            logits_scaling=float(config["logits_scaling"]),
            shared_width=config["shared_intermediate_size"],
            experts_held=None if held == published else (first, held),
            layer_types=kinds, mamba_heads=config["mamba_n_heads"],
            mamba_head_dim=config["mamba_d_head"],
            mamba_state=config["mamba_d_state"],
            mamba_conv=config["mamba_d_conv"],
            mamba_chunk=config["mamba_chunk_size"])
        self.module = DecoderLM(self.cfg)
        # the benchmark's own choice of the random weights' scale (the
        # file's ``assumed``): nothing a served model has
        self.embed_range = float(config.get("embed_init_range",
                                            config["initializer_range"]))
        self.final_norm = float(config.get("final_norm_init", 1.0))
        self.n_layer, self.n_head = self.cfg.n_layer, n_head
        self.head_dim = self.cfg.head_dim
        self.vocab_size = self.cfg.vocab_size

    def sizes(self):
        c = self.cfg
        w, n = c.mamba_heads * c.mamba_head_dim, c.mamba_state
        mamba = (c.hidden_size * (2 * w + 2 * n + c.mamba_heads)
                 + (w + 2 * n) * (c.mamba_conv + 1) + 3 * c.mamba_heads + w
                 + w * c.hidden_size)
        attention = 2 * c.hidden_size * c.n_embd \
            + 2 * c.hidden_size * c.n_kv * c.head_dim
        every = (2 * c.hidden_size + c.hidden_size * c.n_experts
                 + c.held[1] * 3 * c.hidden_size * c.expert_width
                 + 3 * c.hidden_size * c.shared_width)
        from deepspeed_tpu.inference.kv_pool import slot_state_nbytes

        return {"num_hidden_layers": c.n_layer, "hidden_size": c.hidden_size,
                "layer_types": list(c.kinds), "heads": c.n_head,
                "kv_heads": c.n_kv, "head_dim": c.head_dim,
                "router_outputs": c.n_experts, "experts_held": list(c.held),
                "num_experts_per_tok": c.experts_per_token,
                "intermediate_size": c.expert_width,
                "shared_intermediate_size": c.shared_width,
                "mamba": [c.mamba_heads, c.mamba_head_dim, c.mamba_state],
                "vocab_size": c.vocab_size,
                "state_bytes_per_slot": slot_state_nbytes(
                    self.module_cache_spec()),
                "params": len(c.mamba_layers) * mamba
                + len(c.kv_layers) * attention + c.n_layer * every
                + c.vocab_size * c.hidden_size + c.hidden_size}

    def module_cache_spec(self):
        from deepspeed_tpu.models.decoder import cache_spec

        return cache_spec(self.cfg)

    def init_params(self, seed, on_host=False):
        """Random weights from the seed in the type they are served in, made
        in one jitted program on the default device. The seed is an argument
        of that program, so that one cached program serves every seed."""
        return jax.jit(lambda key: rescaled(
            self.module.init(key)["params"], self.embed_range
            / self.cfg.initializer_range, self.final_norm))(
            jax.random.PRNGKey(seed))

    def kv_bytes_per_token_layer(self):
        """A key and a value for every STORED head, in the type the engine
        stores, in a layer that holds keys (one in ten here)."""
        return 2 * self.cfg.n_kv * self.head_dim * self.cfg.dtype.itemsize

    def reference_logits(self, params, ids):
        """The reference's logits, and (module comment above) the two
        comparisons on identical inputs made on the way: where one fails, no
        token of the logits returned is within the driver's margin, so the
        run is not ``correct``."""
        from benchmark.harness import note

        held = Precision(params, self.cfg)
        out = reference_logits(params, ids, self.cfg, watch=held.watch)
        # what the serve driver's fixed margin is worth here (PERF.md)
        note(event="reference_logits", shape=list(out.shape),
             std_over_vocab=float(out[0].std(axis=-1).mean()),
             std=float(out[0].std()))
        readings = held.readings()
        note(event="precision", state_limit=STATE_LIMIT,
             router_limit=ROUTER_LIMIT, held=held.ok(), **readings)
        return out if held.ok() else refused(out, np.asarray(ids))


class Precision(object):
    """The two comparisons of the module comment, fed by the reference's
    ``watch`` a layer and a sequence at a time."""

    def __init__(self, params, cfg):
        self.params, self.cfg = params, cfg
        self.state, self.router = [], []

    def watch(self, layer, sequence, seen):
        from deepspeed_tpu.models import decoder

        got = decoder.router_logits(
            seen["ffn_in"], self.params["layers"]["router"][layer])
        self.router.append(float(abs(got - seen["router_logits"]).max()))
        if layer in self.cfg.mamba_layers:
            self.state.append(float(state_error(self.cfg, seen).max()))

    def readings(self):
        return {"state_rel_err": max(self.state) if self.state else None,
                "router_logit_err": max(self.router)}

    def ok(self):
        r = self.readings()
        return r["router_logit_err"] <= ROUTER_LIMIT and (
            r["state_rel_err"] is None or r["state_rel_err"] <= STATE_LIMIT)


def state_error(cfg, seen):
    """A head's relative error [heads] of the PROGRAM's recurrence on the
    reference's inputs of one Mamba layer and sequence (module comment)."""
    from deepspeed_tpu.models import mamba2

    (_, shape, dtype), = [s for s in mamba2.state_shapes(cfg)
                          if s[0] == mamba2.ssm_key(0)]
    x, dt, bmat, cmat = (seen[k][None] for k in ("x", "dt", "B", "C"))
    lane = min(3 * cfg.mamba_chunk // 2, x.shape[1] // 2)
    got = _recurrence(x, dt, bmat, cmat, seen["A"], shape=tuple(shape),
                      dtype=jnp.dtype(dtype), lane=lane,
                      chunk=cfg.mamba_chunk)
    want = seen["state"]                                   # [H, P, N]
    return np.asarray(jnp.sqrt(
        jnp.sum(jnp.square(got - want), axis=(1, 2))
        / jnp.sum(jnp.square(want), axis=(1, 2))))


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "lane",
                                             "chunk"))
def _recurrence(x, dt, bmat, cmat, a, shape, dtype, lane, chunk):
    """The state [H, P, N] float32 after one sequence (a batch of 1): the
    first ``lane`` tokens through the chunked form, the rest a token at a
    time, carried as ``mamba2.mixer`` carries it: computed in float32, kept
    in the pool's type."""
    from deepspeed_tpu.models import mamba2

    _, state = mamba2.ssd(x[:, :lane], dt[:, :lane], a, bmat[:, :lane],
                          cmat[:, :lane],
                          jnp.zeros((1,) + shape, jnp.float32), chunk)

    def token(state, inputs):
        x_t, dt_t, b_t, c_t = inputs
        _, state32 = mamba2.step(x_t, dt_t, a, b_t, c_t,
                                 state.astype(jnp.float32))
        return state32.astype(dtype), None

    state, _ = jax.lax.scan(token, state.astype(dtype), tuple(
        jnp.moveaxis(v[:, lane:], 1, 0) for v in (x, dt, bmat, cmat)))
    h, p = x.shape[2:]
    # the program keeps [N, heads x P], the reference [heads, P, N]
    return state[0].astype(jnp.float32).reshape(-1, h, p).transpose(1, 2, 0)


def refused(out, ids):
    """``out`` [B, T, V] with ``REFUSED`` added, at every position, to a
    token that is NOT the one the stream holds next: the margin of every
    served token then reads at least about ``REFUSED``."""
    nxt = np.roll(ids, -1, axis=1)
    b, t = np.indices(ids.shape)
    out[b, t, (nxt + 1) % out.shape[-1]] += REFUSED
    return out


def rescaled(params, table, last_norm):
    """``params`` with the token table times ``table`` and the last norm's
    weight at ``last_norm``: where the benchmark sets the spread of its
    random weights' logits (the configuration's ``embed_init_range`` and
    ``final_norm_init``, with their reasons under ``assumed``)."""
    return dict(params, embed=params["embed"] * table,
                final_norm=params["final_norm"] * last_norm)


class Experts(object):
    """One layer's routed experts under a published name: ``self[e]`` is
    expert ``e``'s matrix, sliced out of the program's stack when asked (a
    layer's experts whole are 0.68 GB at the cell's widths, beside an engine
    that fills the chip); a slice of it is the same over fewer experts."""

    def __init__(self, stack, layer, columns, experts=None):
        self.stack, self.layer, self.columns = stack, layer, columns
        self.experts = range(stack.shape[1]) if experts is None else experts

    def __len__(self):
        return len(self.experts)

    def __getitem__(self, e):
        if isinstance(e, slice):
            return Experts(self.stack, self.layer, self.columns,
                           self.experts[e])
        return self.stack[self.layer, self.experts[e]][:, self.columns]


def published_names(params, cfg):
    """The program's tree under the reference's names. ``layers`` is a
    generator: one layer's slices exist at a time, and of its routed experts
    one expert's (``Experts``)."""
    f, fs = cfg.expert_width, cfg.shared_width
    q_w, kv_w = cfg.n_embd, cfg.n_kv * cfg.head_dim

    def layers():
        n_attn = n_mamba = 0
        for i, kind in enumerate(cfg.kinds):
            stacks = params["layers"]
            p = {k: v[i] for k, v in stacks.items()
                 if k not in ("w_gate_up", "w_down")}
            out = {"input_layernorm": p["attn_norm"],
                   "post_attention_layernorm": p["ffn_norm"],
                   "router": p["router"],
                   "gate_proj": Experts(stacks["w_gate_up"], i, slice(0, f)),
                   "up_proj": Experts(stacks["w_gate_up"], i,
                                      slice(f, 2 * f)),
                   "down_proj": Experts(stacks["w_down"], i, slice(None)),
                   "shared_gate": p["shared_gate_up"][:, :fs],
                   "shared_up": p["shared_gate_up"][:, fs:],
                   "shared_down": p["shared_down"]}
            if kind == "mamba":
                out.update({k: v[n_mamba]
                            for k, v in params["mamba"].items()})
                n_mamba += 1
            else:
                a = {k: v[n_attn] for k, v in params["attn"].items()}
                out.update(q_proj=a["wqkv"][:, :q_w],
                           k_proj=a["wqkv"][:, q_w:q_w + kv_w],
                           v_proj=a["wqkv"][:, q_w + kv_w:], o_proj=a["wo"])
                n_attn += 1
            yield out

    return {"embed_tokens": params["embed"], "layers": layers(),
            "norm": params["final_norm"]}


def hyper(cfg):
    """What the reference is told beside the weights."""
    return {"layer_types": cfg.kinds, "n_head": cfg.n_head, "n_kv": cfg.n_kv,
            "attention_multiplier": cfg.attn_scale,
            "mamba_heads": cfg.mamba_heads, "d_state": cfg.mamba_state,
            "top_k": cfg.experts_per_token, "held": cfg.held,
            "eps": cfg.rms_norm_eps,
            "embedding_multiplier": cfg.embedding_multiplier,
            "residual_multiplier": cfg.residual_multiplier,
            "logits_scaling": cfg.logits_scaling}


def reference_logits(params, ids, cfg, with_gaps=False, watch=None):
    """The plain reference on the program's parameter tree, for a
    ``DecoderConfig`` ``cfg`` (the tests call it at a tiny size)."""
    return reference.logits(published_names(params, cfg), ids, hyper(cfg),
                            with_gaps=with_gaps, watch=watch)
