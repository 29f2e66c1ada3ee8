"""Configurations of ``"model_type": "jamba"`` (AI21-Jamba2-3B): the program's
config-driven decoder block (``deepspeed_tpu/models/decoder.py``) with Mamba-1
selective-scan layers (``models/mamba1.py``: a ``[16, 5120]`` float32 state
and a three-row tail a slot) beside multi-query attention layers without
positions (20 query heads over ONE stored head, a paged cache as deep as the
attention layers only) and a dense gated feed-forward in EVERY layer (a stack
without experts); its weights from the seed, its plain reference and its
account of the cache. Serving only: it owes what the ``serve`` driver asks
and nothing of training (benchmark/README.md, "What a builder owes").
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness
from benchmark.reference import jamba as reference

# The refusal and the error measure are DeepSeek-V3's builder's: its
# functions, not copies of them.
shared = harness.load_by_name("model_builders", "deepseek_v3")

# WHAT HOLDS THE PRECISION THE CONFIGURATION STATES (its ``assumed``: a
# float32 state, a bf16 tail, float32 from ``x_proj``'s output through the
# scan, bf16 keys and values a token, a float32 residual stream). The serve driver's one limit, the
# token margin, sees a wrong token, a wrong stream or a layer gone astray, but
# not a state, a tail, a step or a cache rounded a precision lower: those
# move the logits by less than the program's bf16 activations do (Granite's
# builder, PERF.md PR 33). So five quantities are held on IDENTICAL inputs,
# at the cell's widths, on the checked sequences: the program's own functions
# are handed what the reference computed and must return what the reference
# returns. There is no router here, so no near-tie and NO exemption: every
# served position is held to the driver's margin. Each limit lies between
# what the sound program reads and what reads when the quantity is computed
# in the precision below (``benchmark/probe_jamba.py`` plants each and reads
# both; PERF.md, PR 48):
#   state: the relative error (Frobenius, the whole ``[N, W]`` state) of a
#     Mamba layer's state after the last token, the program's recurrence
#     (``mamba1.scan`` over the first ``LANE`` tokens, then ``mamba1.step`` a
#     token at a time, the state carried in the type ``mamba1.state_shapes``
#     gives the pool) against the reference's token-by-token scan on the
#     reference's x, dt, B, C; below: the state carried in bf16.
#   tail: the largest relative error (Euclidean, a row) of the three rows a
#     slot keeps of a Mamba layer's ``x`` before the convolution,
#     ``mamba1.mixer`` on the reference's normed stream as a prompt's end and
#     its answer are served (the last ``TAIL_TOKENS`` tokens: all but
#     ``TAIL_STEPS`` one slice from an empty tail, the rest a token at a
#     time, the tail carried in the pool's type), against the reference's
#     float32 rows; below: the tail carried in ``float8_e4m3fn``.
#   dt: the largest relative error (Euclidean, a token) of the step a
#     channel after the softplus, ``mamba1.selection`` on the reference's
#     convolved stream, against the reference's; below: ``x_proj``'s output,
#     the three norms' and ``dt_proj``'s rounded to bf16.
#   attention: the largest relative error (Euclidean, a token) of what an
#     attention layer ADDS to the stream, ``decoder.attention_mix`` through
#     the program's own ``CacheAttention`` on a PAGED POOL of one stored head
#     of 128 (``program_attention``: the whole sequence a lane slice of
#     ``PROBE_PAGE`` tokens at a time, then one decode step of a row a page;
#     on the chip ``kv_append``, ``prefill_attn`` and ``paged_decode`` at
#     ``rep = 20`` with eight pages a unit), against the reference's on the
#     reference's normed stream; below: the keys and values rounded to
#     ``float8_e4m3fn`` as they are written.
#   stream: the largest relative error (Frobenius, a layer and a sequence)
#     of what a layer's feed-forward LEAVES ADDED to the stream:
#     ``decoder.dense_ffn`` on the reference's stream, carried in the type
#     the program says its stream has (``cfg.stream_dtype``), less that
#     stream, against what the reference's feed-forward adds. A float32
#     stream reads the branch's own bf16 inputs (2.9e-3 on every seed);
#     below: the stream in bf16, rounded on the way in and as the branch is
#     added, 2**-9 of a stream several times the branch beside it (1.4e-2).
#     The limit stands near the sound reading, which moves by a third of a
#     percent a seed, because the feed-forward's sums ALONE in bf16 under a
#     float32 stream read 3.9e-3 and are held too. (What a Mamba mixer's
#     ``out_proj`` emits is not held: rounded to bf16 it moves its branch by
#     less than the branch's bf16 inputs already do.)
STATE_LIMIT = 2e-3
TAIL_LIMIT = 1e-2
DT_LIMIT = 1.5e-3
ATTENTION_LIMIT = 1.5e-2
STREAM_LIMIT = 3.4e-3
PROBE_PAGE = shared.PROBE_PAGE
# the lane's slice (the cell's ``prefill_chunk``): a prompt is one of them
LANE = 128
TAIL_TOKENS, TAIL_STEPS = 32, 8


class Model(object):
    def __init__(self, config):
        from deepspeed_tpu.models import decoder

        if "mamba1" not in getattr(decoder, "RECURRENT", ()):
            raise RuntimeError(
                "this program has no Mamba-1 selective scan "
                "(deepspeed_tpu/models/mamba1.py): it cannot build "
                "model_type jamba")
        for key, published in (
                ("hidden_act", "silu"), ("mamba_conv_bias", True),
                ("mamba_proj_bias", False), ("num_experts", 1),
                ("sliding_window", None), ("tie_word_embeddings", True)):
            if config[key] != published:
                raise ValueError("model_builders/jamba.py builds {}={!r} "
                                 "only".format(key, published))
        n_layer, n_head = config["num_hidden_layers"], \
            config["num_attention_heads"]
        if config["hidden_size"] % n_head:
            raise ValueError("a head is hidden_size / num_attention_heads")
        self.cfg = decoder.DecoderConfig(
            vocab_size=config["vocab_size"], n_layer=n_layer, n_head=n_head,
            head_dim=config["hidden_size"] // n_head,
            hidden_size=config["hidden_size"],
            n_positions=config["max_position_embeddings"],
            # num_experts 1: the family builds a dense MLP in every layer
            n_experts=0, experts_per_token=0, expert_width=0,
            rms_norm_eps=config["rms_norm_eps"], qk_norm=False,
            tie_word_embeddings=True,
            dtype=jnp.dtype(config["deployment"]["compute_dtype"]),
            initializer_range=config["initializer_range"],
            n_kv_head=config["num_key_value_heads"], rope=False,
            layer_types=tuple(
                "attention" if k == "attention" else "mamba1"
                for k in reference.layer_kinds(
                    n_layer, config["attn_layer_period"],
                    config["attn_layer_offset"])),
            dense_layers=n_layer, dense_width=config["intermediate_size"],
            mamba_state=config["mamba_d_state"],
            mamba_conv=config["mamba_d_conv"],
            mamba_expand=config["mamba_expand"],
            mamba_dt_rank=config["mamba_dt_rank"],
            residual_fp32=config["deployment"].get(
                "residual_dtype", config["deployment"]["compute_dtype"])
            == "float32")
        self.module = decoder.DecoderLM(self.cfg)
        # the benchmark's own choice of its random weights' scale (the
        # file's ``assumed``): nothing a served model has
        self.embed_range = float(config.get("embed_init_range",
                                            config["initializer_range"]))
        self.final_norm = float(config.get("final_norm_init", 1.0))
        self.n_layer, self.n_head = self.cfg.n_layer, n_head
        self.head_dim = self.cfg.head_dim
        self.vocab_size = self.cfg.vocab_size

    def sizes(self):
        from deepspeed_tpu.inference.kv_pool import slot_state_nbytes
        from deepspeed_tpu.models.decoder import cache_spec

        c = self.cfg
        w, n, r = c.mamba_expand * c.hidden_size, c.mamba_state, \
            c.mamba_dt_rank
        mamba = c.hidden_size * 2 * w + (c.mamba_conv + 1) * w \
            + w * (r + 2 * n) + (r + 1) * w + r + 2 * n + w * n + w \
            + w * c.hidden_size
        attention = 2 * c.hidden_size * c.n_embd \
            + 2 * c.hidden_size * c.n_kv * c.head_dim
        every = 3 * c.hidden_size * c.dense_width + 2 * c.hidden_size
        spec = cache_spec(c)
        return {"num_hidden_layers": c.n_layer, "hidden_size": c.hidden_size,
                "layer_types": list(c.kinds), "heads": c.n_head,
                "kv_heads": c.n_kv, "head_dim": c.head_dim,
                "intermediate_size": c.dense_width,
                "mamba": [w, n, r, c.mamba_conv],
                "vocab_size": c.vocab_size, "kv_layers": spec.n_layer,
                "state_bytes_per_slot": slot_state_nbytes(spec),
                "params": len(c.mamba1_layers) * mamba
                + len(c.kv_layers) * attention + c.n_layer * every
                + c.vocab_size * c.hidden_size + c.hidden_size}

    def init_params(self, seed, on_host=False):
        """Random weights from the seed in the type they are served in, made
        in one jitted program on the default device. The seed is an argument
        of that program, so that one cached program serves every seed."""
        return jax.jit(lambda key: rescaled(
            self.module.init(key)["params"], self.embed_range
            / self.cfg.initializer_range, self.final_norm))(
            jax.random.PRNGKey(seed))

    def kv_bytes_per_token_layer(self):
        """A key and a value for the ONE stored head, in the type the engine
        stores, in a layer that holds keys (2 of the 28 here)."""
        return 2 * self.cfg.n_kv * self.head_dim * self.cfg.dtype.itemsize

    def reference_logits(self, params, ids):
        """The reference's logits for the served streams ``ids``, and
        (module comment above) the comparisons on identical inputs made on
        the way: where one fails, no token of the logits returned is within
        the driver's margin, so the run is not ``correct``. No position is
        exempt."""
        ids = np.asarray(ids)
        held = Precision(params, self.cfg)
        out = reference_logits(params, ids, self.cfg, watch=held.watch)
        # what the serve driver's fixed margin is worth here (PERF.md)
        harness.note(event="reference_logits", shape=list(out.shape),
                     std_over_vocab=float(out[0].std(axis=-1).mean()),
                     std=float(out[0].std()))
        harness.note(event="precision", held=held.ok(),
                     limits=dict(Precision.LIMITS), exempt_positions=0,
                     **held.readings())
        return out if held.ok() else shared.refused(out, ids)


def rescaled(params, table, last_norm):
    """``params`` with the tied token table times ``table`` and the last
    norm's weight at ``last_norm``: where the benchmark sets the spread of
    its random weights' logits (the configuration's ``embed_init_range`` and
    ``final_norm_init``, with their reasons under ``assumed``)."""
    return dict(params, embed=params["embed"] * table,
                final_norm=params["final_norm"] * last_norm)


class Precision(object):
    """The comparisons of the module comment, fed by the reference's
    ``watch`` a layer and a sequence at a time."""

    LIMITS = (("state_rel_err", STATE_LIMIT), ("tail_rel_err", TAIL_LIMIT),
              ("dt_rel_err", DT_LIMIT),
              ("attention_rel_err", ATTENTION_LIMIT),
              ("stream_rel_err", STREAM_LIMIT))

    def __init__(self, params, cfg):
        self.params, self.cfg = params, cfg
        self.seen = {name: [] for name, _ in self.LIMITS}

    def watch(self, layer, sequence, seen):
        cfg = self.cfg
        self.seen["stream_rel_err"].append(stream_error(
            dict({k: v[layer] for k, v in self.params["dense"].items()},
                 ffn_norm=self.params["layers"]["ffn_norm"][layer]),
            cfg, seen))
        if cfg.kinds[layer] == "mamba1":
            weights = {k: v[cfg.mamba1_layers.index(layer)]
                       for k, v in self.params["mamba1"].items()}
            self.seen["state_rel_err"].append(state_error(cfg, seen))
            self.seen["tail_rel_err"].append(shared.latent_error(
                program_tail(weights, cfg, seen["mix_in"]),
                seen["x_in"][-(cfg.mamba_conv - 1):]))
            self.seen["dt_rel_err"].append(shared.latent_error(
                program_dt(weights, cfg, seen["x"]), seen["dt"]))
        else:
            weights = {k: v[cfg.kv_layers.index(layer)]
                       for k, v in self.params["attn"].items()}
            self.seen["attention_rel_err"].extend(
                shared.latent_error(got, seen["mix_out"][at])
                for got, at in program_attention(weights, cfg,
                                                 seen["mix_in"]))

    def readings(self):
        return {name: max(v) if v else None for name, v in self.seen.items()}

    def ok(self):
        r = self.readings()
        return all(r[name] is None or r[name] <= limit
                   for name, limit in self.LIMITS)


def _pool_type(cfg, key):
    from deepspeed_tpu.models import mamba1

    (_, shape, dtype), = [s for s in mamba1.state_shapes(cfg) if s[0] == key]
    return tuple(shape), jnp.dtype(dtype)


def state_error(cfg, seen):
    """The relative error of the PROGRAM's recurrence on the reference's
    inputs of one Mamba layer and sequence (module comment)."""
    from deepspeed_tpu.models import mamba1

    _, dtype = _pool_type(cfg, mamba1.ssm_key(0))
    x, dt, bmat, cmat = (seen[k][None] for k in ("x", "dt", "B", "C"))
    got = _recurrence(x, dt, bmat, cmat, seen["A"].T, dtype=dtype,
                      lane=min(LANE, x.shape[1] // 2))
    want = seen["state"].T                                  # [N, W]
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


@functools.partial(jax.jit, static_argnames=("dtype", "lane"))
def _recurrence(x, dt, bmat, cmat, a, dtype, lane):
    """The state [N, W] float32 after one sequence (a batch of 1): the first
    ``lane`` tokens through the prompt form, the rest a token at a time,
    carried as ``mamba1.mixer`` carries it: computed in float32, kept in the
    pool's type."""
    from deepspeed_tpu.models import mamba1

    _, state = mamba1.scan(x[:, :lane], dt[:, :lane], a, bmat[:, :lane],
                           cmat[:, :lane], jnp.zeros((1,) + a.shape,
                                                     jnp.float32))

    def token(state, inputs):
        x_t, dt_t, b_t, c_t = inputs
        _, state32 = mamba1.step(x_t, dt_t, a, b_t, c_t,
                                 state.astype(jnp.float32))
        return state32.astype(dtype), None

    state, _ = jax.lax.scan(token, state.astype(dtype), tuple(
        jnp.moveaxis(v[:, lane:], 1, 0) for v in (x, dt, bmat, cmat)))
    return state[0].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("cfg", "dtype"))
def _tail(weights, mix_in, cfg, dtype):
    from deepspeed_tpu.models import mamba1

    shape, _ = _pool_type(cfg, mamba1.conv_key(0))
    n, w = cfg.mamba_state, mamba1.width(cfg)
    h = mix_in[None].astype(cfg.dtype)
    lane = h.shape[1] - min(TAIL_STEPS, h.shape[1] // 2)
    _, ssm, tail = mamba1.mixer(
        weights, cfg, h[:, :lane], jnp.zeros((1, n, w), jnp.float32),
        jnp.zeros((1,) + shape, dtype), jnp.zeros((1,), jnp.int32),
        jnp.asarray([lane], jnp.int32))

    def token(carry, x):
        h_t, pos = x
        _, ssm, tail = mamba1.mixer(weights, cfg, h_t[:, None], *carry,
                                    pos[None], jnp.ones((1,), jnp.int32))
        return (ssm, tail), None

    (_, tail), _ = jax.lax.scan(token, (ssm, tail), (
        jnp.moveaxis(h[:, lane:], 1, 0),
        lane + jnp.arange(h.shape[1] - lane)))
    return tail[0].astype(jnp.float32)


def program_tail(weights, cfg, mix_in, dtype=None):
    """The rows a slot would keep of one Mamba layer's ``x``, from the end
    of the reference's normed stream ``mix_in`` [T, C] (cast to the compute
    type, as the program's own norm hands it on) through ``mamba1.mixer`` as
    a request is served (module comment): [K - 1, W] float32. ``dtype``: the
    type the tail is carried in, the pool's own unless given."""
    from deepspeed_tpu.models import mamba1

    _, tail_dtype = _pool_type(cfg, mamba1.conv_key(0))
    return _tail(weights, mix_in[-TAIL_TOKENS:], cfg,
                 jnp.dtype(dtype or tail_dtype))


@functools.partial(jax.jit, static_argnames=("cfg",))
def program_dt(weights, cfg, x):
    """The step a channel [T, W] the PROGRAM takes from the reference's
    convolved stream ``x`` [T, W]: ``mamba1.selection``."""
    from deepspeed_tpu.models import mamba1

    return mamba1.selection(weights, cfg, x)[0]


@functools.partial(jax.jit, static_argnames=("cfg",))
def program_ffn(layer, cfg, stream):
    """What the PROGRAM's feed-forward leaves added to the reference's
    stream ``stream`` [T, C] float32: ``decoder.dense_ffn`` on it in the
    type the program carries its stream in, less the stream as the reference
    had it: [T, C] float32. The barriers make the carried stream real on
    both sides, as a step's layers hand it on: left to itself the compiler
    keeps the excess precision of a rounding it can fuse away."""
    from deepspeed_tpu.models import decoder

    carried = jax.lax.optimization_barrier(
        stream[None].astype(cfg.stream_dtype))
    after = jax.lax.optimization_barrier(
        decoder.dense_ffn(layer, cfg, carried))
    return (after.astype(jnp.float32) - stream[None])[0]


def stream_error(layer, cfg, seen):
    """The relative error of what one layer's feed-forward leaves added to
    the stream (module comment)."""
    got = program_ffn(layer, cfg, seen["ff_in"])
    return float(jnp.linalg.norm(got - seen["ff_out"])
                 / jnp.linalg.norm(seen["ff_out"]))


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
        y, (k, v) = decoder.attention_mix(weights, cfg, h, 0, None, attend,
                                          attend.planes)
        return y.astype(jnp.float32), k, v

    return jax.jit(run, donate_argnums=(2, 3))


def program_attention(weights, cfg, mix_in):
    """What the PROGRAM's attention adds to the stream for one sequence
    through a paged pool as the engine holds one (ONE stored head a page,
    page 0 the trash page, a table a row): first the whole sequence as the
    LANE serves a prompt, a slice of ``PROBE_PAGE`` tokens at a time, then
    ONE DECODE STEP of one row a page (``model_builders/deepseek_v3.py``
    ``program_attention``, whose walk this is): [(y [n, C] float32, the
    positions it stands for)]."""
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
    """Drop the compiled probes: a caller that plants another precision in
    the program (``benchmark/probe_jamba.py``) has them traced again."""
    _mix.cache_clear()
    for compiled in (_recurrence, _tail, program_dt, program_ffn):
        compiled.clear_cache()


def published_names(params, cfg):
    """The program's tree under the reference's names: ``wqkv`` cut into the
    three projections it holds, ``w_gate_up`` into the gate and the up
    matrix, ``A_log`` turned ``[W, N]`` as the family stores it. ``layers``
    is a generator: one layer's slices exist at a time."""
    f = cfg.dense_width
    q_w, kv_w = cfg.n_embd, cfg.n_kv * cfg.head_dim

    def layers():
        n = {"mamba1": 0, "attention": 0}
        for i, kind in enumerate(cfg.kinds):
            dense = {k: v[i] for k, v in params["dense"].items()}
            out = {"input_layernorm": params["layers"]["attn_norm"][i],
                   "pre_ff_layernorm": params["layers"]["ffn_norm"][i],
                   "gate_proj": dense["w_gate_up"][:, :f],
                   "up_proj": dense["w_gate_up"][:, f:],
                   "down_proj": dense["w_down"]}
            if kind == "mamba1":
                a = {k: v[n[kind]] for k, v in params["mamba1"].items()}
                out.update(
                    {k: a[k] for k in ("in_proj", "conv_w", "conv_b",
                                       "x_proj", "dt_proj", "dt_bias", "D",
                                       "out_proj")},
                    dt_layernorm=a["dt_norm"], b_layernorm=a["b_norm"],
                    c_layernorm=a["c_norm"], A_log=a["A_log"].T)
            else:
                a = {k: v[n[kind]] for k, v in params["attn"].items()}
                out.update(q_proj=a["wqkv"][:, :q_w],
                           k_proj=a["wqkv"][:, q_w:q_w + kv_w],
                           v_proj=a["wqkv"][:, q_w + kv_w:], o_proj=a["wo"])
            n[kind] += 1
            yield out

    return {"embed_tokens": params["embed"], "layers": layers(),
            "final_layernorm": params["final_norm"]}


def hyper(cfg):
    """What the reference is told beside the weights."""
    return {"layer_types": tuple("attention" if k == "attention" else "mamba"
                                 for k in cfg.kinds),
            "n_head": cfg.n_head, "n_kv": cfg.n_kv,
            "d_state": cfg.mamba_state, "dt_rank": cfg.mamba_dt_rank,
            "eps": cfg.rms_norm_eps}


def reference_logits(params, ids, cfg, watch=None):
    """The plain reference on the program's parameter tree, for a
    ``DecoderConfig`` ``cfg`` (the tests call it at a tiny size)."""
    return reference.logits(published_names(params, cfg), ids, hyper(cfg),
                            watch=watch)
