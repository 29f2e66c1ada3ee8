"""Configurations of ``"model_type": "nemotron_h"`` (NVIDIA-Nemotron-3-Nano):
the program's config-driven decoder block (``deepspeed_tpu/models/decoder.py``)
as a ONE-BRANCH stack (a layer is a Mamba-2 mixer with eight groups, OR the
chip's share of ungated relu2 experts beside a shared one, OR grouped-query
attention without positions; one norm a layer), built from the published keys
and the share the file states; its weights from the seed, its plain reference
and its account of the cache. Serving only: it owes what the ``serve`` driver
asks and nothing of training (benchmark/README.md, "What a builder owes").
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness
from benchmark.reference import nemotron_h as reference

# The refusal, the exemption, the error measures and the one-expert view of a
# stack are DeepSeek-V3's builder's; the replay through the program's decode
# iteration and the distance of an expert from changing sides LFM2's: their
# functions, not copies of them.
lfm2 = harness.load_by_name("model_builders", "lfm2_moe")
shared = lfm2.shared

# WHAT HOLDS THE PRECISION THE CONFIGURATION STATES (its ``assumed``: a float32
# recurrent state, a float32 router from the float32 norm, a float32 residual
# stream). The serve driver's one limit, the token margin, cannot: the
# program's bf16 activations make as much noise in the logits as a state or a
# router a precision lower does (Granite's builder, PERF.md PR 33). So three
# quantities are held on IDENTICAL inputs, at the cell's widths, on the
# checked sequences: the program's own functions are handed what the
# reference computed and must return what the reference returns. Each limit
# lies between what the sound program reads and what reads when the quantity
# is computed in the precision below (my chip runs, PERF.md PR 58, have both
# readings of each):
#   state: the largest relative error (Frobenius, a head) of a Mamba layer's
#     state after the last token, the program's recurrence at EIGHT GROUPS
#     (``mamba2.ssd`` over a chunk and a half, then ``mamba2.step`` a token at
#     a time, the state carried in the type ``mamba2.state_shapes`` gives the
#     pool) against the reference's token-by-token scan on the reference's x,
#     dt, B, C; below: the state carried in bf16.
#   router: the largest difference of a router logit,
#     ``decoder.router_logits`` against the reference's on the reference's
#     normed stream; below: the matmul in bf16.
#   stream: the largest relative error (Frobenius, a layer and a sequence) of
#     what an expert layer LEAVES ADDED to the stream: ``decoder.moe`` on the
#     reference's stream, carried in the type the program says its stream has
#     (``cfg.stream_dtype``), less that stream, against what the reference's
#     layer adds (its held experts' part and the shared expert's), over the
#     positions where the program's router kept the experts the reference did.
#     Every one of the 23 expert layers is read, the last of them 51 branches
#     deep, where the stream is largest beside the branch; below: the stream
#     carried in bf16 (as published; rounded on the way in and as the branch
#     is added). Sound 4.9e-3 (the branch's own bf16 inputs; the stand-in's
#     size here), a bf16 stream 0.025-0.028 at the cell's widths on the chip.
STATE_LIMIT = 2e-3
ROUTER_LIMIT = shared.ROUTER_LIMIT
STREAM_LIMIT = 1.2e-2

# WHOSE CHOICE OF EXPERTS THE SERVED TOKENS ARE HELD TO (PR 44's rule, LFM2's
# builder). bf16 rounding of the stream moves a router logit, and where the
# float32 reference's own choice of a HELD expert is a near-tie the program
# may keep another: one expert of random weights (weight about 2.5 / 6) is no
# rounding in the logits, and the changed stream flips layers after it. Two
# things repair that, both decided by the REFERENCE's own scores:
#   THE REFERENCE FOLLOWS THE PROGRAM AT A NEAR-TIE. The checked sequences are
#     replayed through the program's own decode iteration (``lfm2.replay``:
#     ``decoder.forward`` a token at a time over a paged pool and the rows'
#     state, the served tokens forced) and ``forward`` says which experts it
#     kept (``aux_moe_choice``). Where a HELD expert changed sides and every
#     held expert that did stands within ``FOLLOW_GAP`` router logits of the
#     edge of the choice by the reference's scores (``lfm2.sides`` with a unit
#     noise: a score's gap from the edge over the root of the two sigmoid
#     slopes' squares, which IS a logit's distance), the reference keeps the
#     program's experts, weighed by its own scores. A held expert that changed
#     sides from further away is NOT followed: the position is held to the
#     reference's own choice and reads over the margin. Experts held elsewhere
#     changing sides among themselves move this chip's sum by the
#     renormalisation alone, continuously: they are neither followed nor
#     counted.
#   A BAND STAYS EXEMPT. The served run is the engine's step, not the replay
#     (``REPLAY_ROWS`` rows against the engine's 64: 64 rows of state are 3.1
#     GB, and the engine still holds the chip while the check runs), and two
#     bf16 executions of one arithmetic may part at a tie closer than their
#     own difference. A position where some held expert, in some layer, stands
#     within ``BAND_GAP`` of the edge is given the served token as the row's
#     largest logit (``shared.exempted``); the precision note says how many
#     positions that was (``exempt_positions`` of ``positions``), how many
#     (layer, position) pairs parted, how many were followed and from how far
#     (``furthest_followed``). EVERY OTHER POSITION IS HELD to the driver's
#     margin at the full spread.
FOLLOW_GAP = 0.08
BAND_GAP = 0.003
REPLAY_ROWS = 8
# What a failed comparison adds to a logit no stream served.
REFUSED = shared.REFUSED


class Model(object):
    def __init__(self, config):
        from deepspeed_tpu.models import decoder

        if "mamba_groups" not in decoder.DecoderConfig._fields:
            raise RuntimeError(
                "this program has no one-branch stack (DecoderConfig has no "
                "mamba_groups / expert_act, layer_types no \"moe\"): it "
                "cannot build model_type nemotron_h")
        for key, published in (
                ("attention_bias", False), ("mamba_hidden_act", "silu"),
                ("mamba_proj_bias", False), ("mlp_bias", False),
                ("mlp_hidden_act", "relu2"), ("n_group", 1),
                ("topk_group", 1), ("n_shared_experts", 1),
                ("use_bias", False), ("use_conv_bias", True),
                ("tie_word_embeddings", False), ("sliding_window", None)):
            if config[key] != published:
                raise ValueError("model_builders/nemotron_h.py builds "
                                 "{}={!r} only".format(key, published))
        kinds = reference.layer_kinds(config["hybrid_override_pattern"])
        if len(kinds) != config["num_hidden_layers"]:
            raise ValueError("hybrid_override_pattern names a branch for "
                             "each of the {} layers".format(
                                 config["num_hidden_layers"]))
        first, held = config.get("experts_held",
                                 (0, config["n_routed_experts"]))
        published = config.get("router_outputs", config["n_routed_experts"])
        if held != config["n_routed_experts"] or first + held > published:
            raise ValueError("n_routed_experts counts the experts held")
        n_head = config["num_attention_heads"]
        deployment = config["deployment"]
        self.pattern = config["hybrid_override_pattern"]
        self.cfg = decoder.DecoderConfig(
            vocab_size=config["vocab_size"],
            n_layer=config["num_hidden_layers"], n_head=n_head,
            head_dim=config["head_dim"], hidden_size=config["hidden_size"],
            n_positions=config["max_position_embeddings"],
            n_experts=published,
            experts_per_token=config["num_experts_per_tok"],
            expert_width=config["moe_intermediate_size"],
            rms_norm_eps=config["layer_norm_epsilon"], qk_norm=False,
            norm_topk_prob=config["norm_topk_prob"],
            tie_word_embeddings=False,
            dtype=jnp.dtype(deployment["compute_dtype"]),
            initializer_range=config["initializer_range"],
            n_kv_head=config["num_key_value_heads"], rope=False,
            shared_width=config["moe_shared_expert_intermediate_size"],
            experts_held=None if held == published else (first, held),
            layer_types=kinds, mamba_heads=config["mamba_num_heads"],
            mamba_head_dim=config["mamba_head_dim"],
            mamba_state=config["ssm_state_size"],
            mamba_conv=config["conv_kernel"],
            mamba_chunk=config["chunk_size"],
            mamba_groups=config["n_groups"],
            # in_proj's 2W + 2GN + H columns are whole lane tiles, or dt's
            # are a matrix apart (DecoderConfig.mamba_dt_apart)
            mamba_dt_apart=(2 * config["mamba_num_heads"]
                            * config["mamba_head_dim"]
                            + 2 * config["n_groups"]
                            * config["ssm_state_size"]
                            + config["mamba_num_heads"]) % 128 != 0,
            expert_act="relu2",
            router_scoring="sigmoid", n_group=1, topk_group=1,
            routed_scaling=float(config["routed_scaling_factor"]),
            residual_fp32=deployment.get(
                "residual_dtype", deployment["compute_dtype"]) == "float32")
        self.module = decoder.DecoderLM(self.cfg)
        # the benchmark's own choice of its random weights' scale (the
        # file's ``assumed``): nothing a served model has
        scale = config["initializer_range"]
        self.scales = (
            float(config.get("embed_init_range", scale)) / scale,
            float(config.get("lm_head_init_range", scale)) / scale,
            float(config.get("final_norm_init", 1.0)),
            float(config.get("router_bias_init_range", 0.0)))
        self.n_layer, self.n_head = self.cfg.n_layer, n_head
        self.head_dim = self.cfg.head_dim
        self.vocab_size = self.cfg.vocab_size

    def param_count(self):
        """From shapes and no weights: the tree ``init`` would make."""
        shapes = jax.eval_shape(self.module.init, jax.random.PRNGKey(0))
        return sum(int(np.prod(leaf.shape))
                   for leaf in jax.tree_util.tree_leaves(shapes))

    def sizes(self):
        from deepspeed_tpu.inference.kv_pool import slot_state_nbytes
        from deepspeed_tpu.models.decoder import cache_spec

        c = self.cfg
        spec = cache_spec(c)
        return {"num_hidden_layers": c.n_layer, "hidden_size": c.hidden_size,
                "hybrid_override_pattern": self.pattern,
                "stack_layers": {k: c.kinds.count(k)
                                 for k in ("mamba", "moe", "attention")},
                "heads": c.n_head, "kv_heads": c.n_kv,
                "head_dim": c.head_dim, "router_outputs": c.n_experts,
                "experts_held": list(c.held),
                "num_experts_per_tok": c.experts_per_token,
                "moe_intermediate_size": c.expert_width,
                "moe_shared_expert_intermediate_size": c.shared_width,
                "mamba": [c.mamba_heads, c.mamba_head_dim, c.mamba_state,
                          c.mamba_groups],
                "vocab_size": c.vocab_size, "kv_layers": spec.n_layer,
                "stream_dtype": str(c.stream_dtype),
                "state_bytes_per_slot": slot_state_nbytes(spec),
                "params": self.param_count()}

    def init_params(self, seed, on_host=False):
        """Random weights from the seed in the type they are served in, made
        in one jitted program on the default device. The seed is an argument
        of that program, so that one cached program serves every seed."""
        return jax.jit(lambda key: rescaled(
            self.module.init(key)["params"], key, *self.scales))(
            jax.random.PRNGKey(seed))

    def kv_bytes_per_token_layer(self):
        """A key and a value for every STORED head, in the type the engine
        stores, in a layer that holds keys (6 of the 52 here)."""
        return 2 * self.cfg.n_kv * self.head_dim * self.cfg.dtype.itemsize

    def reference_logits(self, params, ids):
        """The reference's logits for the served streams ``ids`` (module
        comment above): the program's experts followed where the reference's
        own choice of a held expert is a near-tie, the served token made the
        row's choice in the band that stays exempt, and the three comparisons
        on identical inputs made on the way: where one fails, no token of the
        logits returned is within the driver's margin, so the run is not
        ``correct``."""
        ids = np.asarray(ids)
        held = Precision(params, self.cfg, lfm2.replay(
            params, self.cfg, ids, rows=REPLAY_ROWS))
        out = reference_logits(params, ids, self.cfg, watch=held.watch,
                               follow=held.follow)
        # what the serve driver's fixed margin is worth here (PERF.md)
        harness.note(event="reference_logits", shape=list(out.shape),
                     std_over_vocab=float(out[0].std(axis=-1).mean()),
                     std=float(out[0].std()))
        ties = held.ties(ids.shape)
        harness.note(
            event="precision", held=held.ok(), limits=dict(Precision.LIMITS),
            follow_gap=FOLLOW_GAP, band_gap=BAND_GAP,
            replay_rows=REPLAY_ROWS, positions=int(ties.size),
            exempt_positions=int(ties.sum()),
            exempt_share=float(ties.mean()), **held.parted,
            **held.readings())
        return shared.exempted(out, ids, ties) if held.ok() \
            else shared.refused(out, ids)


def rescaled(params, key, table, head, last_norm, bias):
    """``params`` with the token table times ``table``, the output head times
    ``head``, the last norm's weight at ``last_norm`` and every expert
    layer's selection bias normal at ``bias`` from the seed: where the
    benchmark sets the spread of its random weights' logits and makes the
    bias tell choosing from weighting (the configuration's
    ``embed_init_range``, ``lm_head_init_range``, ``final_norm_init`` and
    ``router_bias_init_range``, with their reasons under ``assumed``)."""
    moe = params["moe"]
    return dict(
        params, embed=params["embed"] * table,
        lm_head=params["lm_head"] * head,
        final_norm=params["final_norm"] * last_norm,
        moe=dict(moe, router_bias=bias * jax.random.normal(
            jax.random.fold_in(key, 38), moe["router_bias"].shape,
            jnp.float32)))


class Precision(object):
    """The comparisons of the module comment, fed by the reference's
    ``watch`` a layer and a sequence at a time, and whose experts the
    reference keeps (``follow``). ``choices``: the experts the program's
    replay kept [expert layers, B, T, k], or None: the reference keeps its
    own and only the band is marked."""

    LIMITS = (("state_rel_err", STATE_LIMIT),
              ("router_logit_err", ROUTER_LIMIT),
              ("stream_rel_err", STREAM_LIMIT))

    def __init__(self, params, cfg, choices=None):
        self.params, self.cfg, self.choices = params, cfg, choices
        self.seen = {name: [] for name, _ in self.LIMITS}
        self.stack = (None, None)            # one expert layer's slices
        self.tied = {}                       # sequence -> [T] bool
        self.parted = {"differ": 0, "followed": 0, "not_followed": 0,
                       "furthest_followed": 0.0, "furthest_parted": 0.0,
                       "replayed": choices is not None}

    def follow(self, layer, sequence, router_logits):
        """``reference.logits``'s ``follow`` (module comment, WHOSE CHOICE):
        the experts the reference is to keep [T, k], and the band marked."""
        cfg, at = self.cfg, self.cfg.moe_layers.index(layer)
        first, count = cfg.held
        inside, far = lfm2.sides(
            np.asarray(router_logits),
            np.asarray(self.params["moe"]["router_bias"][at]),
            cfg.experts_per_token, 1.0)
        held = slice(first, first + count)
        self.tied[sequence] = self.tied.get(sequence, False) \
            | (far[:, held].min(-1) < BAND_GAP)
        if self.choices is None:
            return None
        theirs = np.zeros_like(inside)
        np.put_along_axis(theirs, self.choices[at, sequence], True, axis=-1)
        moved = (theirs != inside)[:, held]
        worst = np.where(moved, far[:, held], 0.0).max(-1)
        differ = moved.any(-1)
        followed = differ & (worst < FOLLOW_GAP)
        p = self.parted
        p["differ"] += int(differ.sum())
        p["followed"] += int(followed.sum())
        p["not_followed"] += int((differ & ~followed).sum())
        p["furthest_parted"] = max(p["furthest_parted"], float(worst.max()))
        if followed.any():
            p["furthest_followed"] = max(p["furthest_followed"],
                                         float(worst[followed].max()))
        own = np.argsort(~inside, axis=-1, kind="stable")[
            :, :cfg.experts_per_token]
        return np.where(followed[:, None], self.choices[at, sequence], own)

    def watch(self, layer, sequence, seen):
        from deepspeed_tpu.models import decoder

        cfg = self.cfg
        if cfg.kinds[layer] == "mamba":
            self.seen["state_rel_err"].append(
                float(state_error(cfg, seen).max()))
        elif cfg.kinds[layer] == "moe":
            if self.stack[0] != layer:  # 0.32 GB of slices, made once a layer
                at = cfg.moe_layers.index(layer)
                self.stack = (layer, dict(
                    {k: v[at] for k, v in self.params["moe"].items()},
                    ffn_norm=self.params["layers"]["norm"][layer]))
            stack = self.stack[1]
            self.seen["router_logit_err"].append(shared.router_error(
                decoder.router_logits(seen["ffn_in"], stack["router"]),
                seen["router_logits"]))
            self.seen["stream_rel_err"].append(stream_error(stack, cfg, seen))

    def ties(self, shape):
        """[B, T] bool: positions in the exempt band in some layer."""
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


def state_error(cfg, seen, dtype=None):
    """A head's relative error [heads] of the PROGRAM's recurrence on the
    reference's inputs of one Mamba layer and sequence (module comment).
    ``dtype``: the type the state is carried in, the pool's own unless
    given."""
    from deepspeed_tpu.models import mamba2

    (_, shape, pool_dtype), = [s for s in mamba2.state_shapes(cfg)
                               if s[0] == mamba2.ssm_key(0)]
    x, dt, bmat, cmat = (seen[k][None] for k in ("x", "dt", "B", "C"))
    if cfg.mamba_groups == 1:
        bmat, cmat = bmat[:, :, 0], cmat[:, :, 0]
    lane = min(3 * cfg.mamba_chunk // 2, x.shape[1] // 2)
    got = _recurrence(x, dt, bmat, cmat, seen["A"], shape=tuple(shape),
                      dtype=jnp.dtype(dtype or pool_dtype), lane=lane,
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
    state = state[0].astype(jnp.float32)
    # the reference keeps [heads, P, N]; the program [N, heads x P], or a
    # group apart [G, N, heads x P / G]
    if state.ndim == 2:
        return state.reshape(-1, h, p).transpose(1, 2, 0)
    g, n = state.shape[:2]
    return state.reshape(g, n, h // g, p).transpose(0, 2, 3, 1).reshape(
        h, p, n)


@functools.partial(jax.jit, static_argnames=("cfg", "dtype"))
def program_branch(stack, cfg, stream, dtype=None):
    """What the PROGRAM's expert layer leaves added to the reference's stream
    ``stream`` [T, C] float32, and the experts its router kept [T, k]:
    ``decoder.moe`` on the stream in the type the program carries its stream
    in (``dtype`` unless None), less the stream as the reference had it. The
    barriers make the carried stream real on both sides, as a step's layers
    hand it on: left to itself the compiler keeps the excess precision of a
    rounding it can fuse away."""
    from deepspeed_tpu.models import decoder

    carried = stream[None].astype(dtype or cfg.stream_dtype)
    carried = jax.lax.optimization_barrier(carried).astype(cfg.stream_dtype)
    chosen = []
    after = decoder.moe(stack, cfg, carried, chosen)[0]
    after = jax.lax.optimization_barrier(after.astype(
        dtype or cfg.stream_dtype))
    return (after.astype(jnp.float32) - stream[None])[0], chosen[0][0]


def stream_error(stack, cfg, seen, dtype=None):
    """The relative error of what one expert layer leaves added to the
    stream, over the positions where the program kept the reference's
    experts (module comment)."""
    got, chosen = program_branch(stack, cfg, seen["stream"], dtype)
    theirs = np.zeros(seen["kept"].shape, bool)
    np.put_along_axis(theirs, np.asarray(chosen), True, axis=-1)
    same = jnp.asarray((theirs == (np.asarray(seen["kept"]) > 0)).all(-1))
    want = seen["branch"]
    return float(jnp.sqrt(
        jnp.sum(jnp.where(same[:, None], jnp.square(got - want), 0.0))
        / jnp.sum(jnp.where(same[:, None], jnp.square(want), 0.0))))


def retrace():
    """Drop the compiled probes and the replay: a caller that plants another
    precision in the program has them traced again."""
    lfm2.retrace()
    for compiled in (_recurrence, program_branch):
        compiled.clear_cache()


def published_names(params, cfg):
    """The program's tree under the reference's (the published) names:
    ``wqkv`` cut into the three projections it holds. ``layers`` is a
    generator: one layer's slices exist at a time, and of its routed experts
    one expert's (``shared.Experts``)."""
    q_w, kv_w = cfg.n_embd, cfg.n_kv * cfg.head_dim
    trees = {"mamba": "mamba", "attention": "attn", "moe": "moe"}

    def layers():
        n = dict.fromkeys(trees, 0)
        for i, kind in enumerate(cfg.kinds):
            at, stacks = n[kind], params[trees[kind]]
            out = {"norm": params["layers"]["norm"][i]}
            if kind == "mamba":
                # the mixer's gated norm beside the layer's own ``norm``
                out.update({"gate_norm" if k == "norm" else k: v[at]
                            for k, v in stacks.items()})
                if "dt_proj" in out:    # the program keeps dt's columns apart
                    out["in_proj"] = jnp.concatenate(
                        [out["in_proj"], out.pop("dt_proj")], axis=1)
            elif kind == "attention":
                wqkv = stacks["wqkv"][at]
                out.update(q_proj=wqkv[:, :q_w],
                           k_proj=wqkv[:, q_w:q_w + kv_w],
                           v_proj=wqkv[:, q_w + kv_w:],
                           o_proj=stacks["wo"][at])
            else:
                out.update(
                    gate=stacks["router"][at],
                    e_score_correction_bias=stacks["router_bias"][at],
                    up_proj=shared.Experts(stacks["w_up"], at, slice(None)),
                    down_proj=shared.Experts(stacks["w_down"], at,
                                             slice(None)),
                    shared_up=stacks["shared_up"][at],
                    shared_down=stacks["shared_down"][at])
            n[kind] += 1
            yield out

    return {"embeddings": params["embed"], "layers": layers(),
            "norm_f": params["final_norm"], "lm_head": params["lm_head"]}


def hyper(cfg):
    """What the reference is told beside the weights."""
    letters = {v: k for k, v in reference.KINDS.items()}
    return {"pattern": "".join(letters[k] for k in cfg.kinds),
            "n_head": cfg.n_head, "n_kv": cfg.n_kv,
            "mamba_heads": cfg.mamba_heads, "n_groups": cfg.mamba_groups,
            "d_state": cfg.mamba_state, "top_k": cfg.experts_per_token,
            "norm_topk_prob": cfg.norm_topk_prob,
            "routed_scaling_factor": cfg.routed_scaling, "held": cfg.held,
            "vocab": (0, cfg.vocab_size), "eps": cfg.rms_norm_eps}


def reference_logits(params, ids, cfg, watch=None, follow=None):
    """The plain reference on the program's parameter tree, for a
    ``DecoderConfig`` ``cfg`` (the tests call it at a tiny size)."""
    return reference.logits(published_names(params, cfg), ids, hyper(cfg),
                            watch=watch, follow=follow)
