"""Configurations of ``"model_type": "phi4flash"`` (Phi-4-mini-flash-reasoning,
the "SambaY" decoder-hybrid-decoder): the program's config-driven decoder block
(``deepspeed_tpu/models/decoder.py``) with Mamba-1 selective-scan layers
WITHOUT inner norms, WINDOW attention layers whose keys live on a ring of
pages a slot, ONE full-attention layer whose plane every cross layer after it
reads, gated memory units fed by the last Mamba layer's scan output, LayerNorm
with a bias and a dense gated feed-forward in every layer; its weights from
the seed, its plain reference and its account of the cache. Serving only: it
owes what the ``serve`` driver asks and nothing of training
(benchmark/README.md, "What a builder owes").
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness
from benchmark.reference import phi4flash as reference

# The refusal and the error measure are DeepSeek-V3's builder's, the state's
# and the stream's comparisons Jamba's: their functions, not copies of them.
shared = harness.load_by_name("model_builders", "deepseek_v3")
jamba = harness.load_by_name("model_builders", "jamba")

# WHAT HOLDS THE PRECISION THE CONFIGURATION STATES (its ``assumed``: a
# float32 state, bf16 keys and values a token on the ring and in the shared
# plane, a float32 memory, a float32 residual stream). The serve driver's one
# limit, the token margin, sees a wrong token, a wrong stream or a layer gone
# astray, but not a cache, a state or a memory rounded a precision lower. So
# five quantities are held on IDENTICAL inputs, at the cell's widths, on the
# checked sequences: the program's own functions are handed what the reference
# computed and must return what the reference returns. No router, so NO
# exemption. Each limit lies between what the sound program reads and what
# reads when the quantity is computed in the precision below
# (``benchmark/probe_phi4flash.py`` plants each and reads both; PERF.md, PR
# 55):
#   window: the largest relative error (Euclidean, a token) of what a WINDOW
#     layer adds to the stream, ``decoder.attention_mix(kind="swa")`` through
#     the program's own ``CacheAttention.windowed`` on a RING of
#     ``ring_pages`` pages of ``PROBE_PAGE`` (``program_window``: the whole
#     sequence a lane slice of ``PROBE_PAGE`` tokens at a time, so that past
#     ``n_ring`` slices every slice overwrites a page the window has left, then
#     one decode step of a row a page at positions all over the sequence, the
#     ring rebuilt as it stood at each: on the chip ``kv_append``,
#     ``window_prefill`` and ``window_decode``), against the reference's
#     masked attention on the reference's normed stream; the checked sequences
#     are ``max_len`` long, past five windows; below: the keys and values
#     rounded to ``float8_e4m3fn`` as they are written.
#   shared: the same of a CROSS layer, ``decoder.attention_mix(kind="xattn")``
#     reading the plane the FULL layer's ``attention_mix`` wrote through the
#     same paged pool (``kv_append`` once, then ``prefill_attn`` /
#     ``paged_decode`` by each reader); below: the full layer's keys and
#     values rounded to ``float8_e4m3fn`` as they are written.
#   gmu: the largest relative error (Euclidean, a token) of what a gated
#     memory unit adds, ``decoder.gmu_mix`` on the reference's normed stream
#     and the reference's memory (float32, as ``forward`` hands it). The
#     program rounds ONCE there: the product ``m * silu(gate)`` goes to
#     ``W_out``'s matmul in the compute type, so a memory handed over in bf16
#     reads within that step's own rounding and no limit separates the two;
#     below is then the precision under the product's: the memory in
#     ``float8_e4m3fn``.
#   state: Jamba's (its builder's ``state_error``, the same recurrence at the
#     same shapes); below: the state carried in bf16.
#   stream: Jamba's comparison (``stream_error``: what a layer's feed-forward
#     leaves added to the stream carried in the type the program says it
#     has), over all 32 layers, under a limit of this family's own (Jamba's
#     3.4e-3 stands a sixth over its sound reading to hold the feed-forward's
#     bf16 sums too; this cell's two readings leave room on both sides):
#     sound is the bf16 feed-forward's own rounding under a float32 stream,
#     2.9e-3; below: the stream carried in bf16 (1.4e-2).
# Readings on the chip (my chip runs, PR 55; sound: 4 x 2,944 positions a run
# of 22 runs, 14 of them for the stream, and the probes' 1,536; below: the
# probes): window 0.0050-0.0066 sound / 0.042-0.052 below; shared
# 0.0047-0.0057 / 0.040-0.043; gmu 0.0029-0.0038 / 0.034-0.037; state 0.0 /
# 0.053; stream 2.91e-3-2.94e-3 / 1.42e-2.
WINDOW_LIMIT = 1.5e-2
SHARED_LIMIT = 1.5e-2
GMU_LIMIT = 6e-3
STATE_LIMIT = jamba.STATE_LIMIT
STREAM_LIMIT = 6e-3
PROBE_PAGE = shared.PROBE_PAGE


class Model(object):
    def __init__(self, config):
        from deepspeed_tpu.models import decoder

        if "sliding_window" not in decoder.DecoderConfig._fields:
            raise RuntimeError(
                "this program has no window group, no cross layer and no "
                "gated memory unit (deepspeed_tpu/models/decoder.py): it "
                "cannot build model_type phi4flash")
        for key, published in (
                ("hidden_act", "silu"), ("mlp_bias", False),
                ("lm_head_bias", False), ("tie_word_embeddings", True),
                ("mb_per_layer", 2)):
            if config[key] != published:
                raise ValueError("model_builders/phi4flash.py builds {}={!r} "
                                 "only".format(key, published))
        n_layer, n_head = config["num_hidden_layers"], \
            config["num_attention_heads"]
        if config["hidden_size"] % n_head or n_layer % 2:
            raise ValueError("a head is hidden_size / num_attention_heads, "
                             "and the stack two halves")
        assumed = config["assumed_sizes"]
        self.cfg = decoder.DecoderConfig(
            vocab_size=config["vocab_size"], n_layer=n_layer, n_head=n_head,
            head_dim=config["hidden_size"] // n_head,
            hidden_size=config["hidden_size"],
            n_positions=config["max_position_embeddings"],
            n_experts=0, experts_per_token=0, expert_width=0,
            rms_norm_eps=config["layer_norm_eps"], qk_norm=False,
            tie_word_embeddings=True,
            dtype=jnp.dtype(config["deployment"]["compute_dtype"]),
            initializer_range=config["initializer_range"],
            n_kv_head=config["num_key_value_heads"], rope=False,
            layer_types=tuple(KINDS[k] for k in reference.layer_kinds(
                n_layer, config["mb_per_layer"])),
            dense_layers=n_layer, dense_width=config["intermediate_size"],
            mamba_state=assumed["mamba_d_state"],
            mamba_conv=assumed["mamba_d_conv"],
            mamba_expand=assumed["mamba_expand"],
            mamba_dt_rank=assumed["mamba_dt_rank"],
            residual_fp32=config["deployment"].get(
                "residual_dtype", config["deployment"]["compute_dtype"])
            == "float32",
            sliding_window=config["sliding_window"], layer_norm=True,
            attn_bias=True, mamba_inner_norms=False)
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
        spec = cache_spec(c)
        kinds = {k: c.kinds.count(k) for k in sorted(set(c.kinds))}
        return {"num_hidden_layers": c.n_layer, "hidden_size": c.hidden_size,
                "layer_kinds": kinds, "heads": c.n_head, "kv_heads": c.n_kv,
                "head_dim": c.head_dim, "intermediate_size": c.dense_width,
                "mamba": [c.mamba_expand * c.hidden_size, c.mamba_state,
                          c.mamba_dt_rank, c.mamba_conv],
                "vocab_size": c.vocab_size, "kv_layers": spec.n_layer,
                "window_layers": spec.window_layers, "window": spec.window,
                "memory_layer": c.memory_layer,
                "state_bytes_per_slot": slot_state_nbytes(spec),
                "params": sum(parameter_count(c).values())}

    def init_params(self, seed, on_host=False):
        """Random weights from the seed in the type they are served in, made
        in one jitted program on the default device. The seed is an argument
        of that program, so that one cached program serves every seed."""
        return jax.jit(lambda key: rescaled(
            self.module.init(key)["params"], self.embed_range
            / self.cfg.initializer_range, self.final_norm))(
            jax.random.PRNGKey(seed))

    def kv_bytes_per_token_layer(self):
        """A key and a value for every stored head, in the type the engine
        stores, in a layer that holds keys (the full one; a window layer
        stores the same a token, for the last ``sliding_window`` only)."""
        return 2 * self.cfg.n_kv * self.head_dim * self.cfg.dtype.itemsize

    def reference_logits(self, params, ids):
        """The reference's logits for the served streams ``ids``, and
        (module comment above) the comparisons on identical inputs made on
        the way: where one fails, no token of the logits returned is within
        the driver's margin, so the run is not ``correct``."""
        ids = np.asarray(ids)
        held = Precision(params, self.cfg)
        out = reference_logits(params, ids, self.cfg, watch=held.watch)
        harness.note(event="reference_logits", shape=list(out.shape),
                     std_over_vocab=float(out[0].std(axis=-1).mean()),
                     std=float(out[0].std()))
        harness.note(event="precision", held=held.ok(),
                     limits=dict(Precision.LIMITS), exempt_positions=0,
                     **held.readings())
        return RowsOnDevice(out if held.ok() else shared.refused(out, ids))


class RowsOnDevice(object):
    """The reference's logits [B, T, V], kept on the HOST (9.4 GB in the
    cell); ``self[k, a:b]`` is those rows as ONE device array, the rows handed
    out before them deleted first. The driver's check reads a request's rows
    twice (``drivers/serve.py`` ``_token_margins``), and a numpy slice went
    to the device once a read: one run of sixteen held two copies of a
    2,048-row slice at once (peak 16.37 of the allocator's 16.91 GB, my chip
    run, PR 55), and two copies of the longest request's 2,816 rows (4.5 GB)
    do not fit beside the engine's 13.1 GB."""

    def __init__(self, host):
        self.host, self.last = host, None
        self.shape, self.dtype = host.shape, host.dtype

    def __getitem__(self, key):
        if self.last is not None:
            self.last.delete()
        self.last = jnp.asarray(self.host[key])
        return self.last


# the reference's words for the kinds -> the program's ``layer_types``
KINDS = {"mamba": "mamba1", "window": "swa", "full": "attention",
         "gmu": "gmu", "cross": "xattn"}


def parameter_count(cfg):
    """Parameters by part, from the configuration alone (the arithmetic of
    the configuration file's ``reduced_why``; ``tests/benchmark`` holds the
    builder's tree to it)."""
    c, f = cfg.hidden_size, cfg.dense_width
    w, n, r = cfg.mamba_expand * c, cfg.mamba_state, cfg.mamba_dt_rank
    q_w, kv_w = cfg.n_embd, cfg.n_kv * cfg.head_dim
    mamba = c * 2 * w + (cfg.mamba_conv + 1) * w + w * (r + 2 * n) \
        + (r + 1) * w + w * n + w + w * c
    attention = c * (q_w + 2 * kv_w) + (q_w + 2 * kv_w) + q_w * c + c
    cross = c * q_w + q_w + q_w * c + c
    every = 3 * c * f + 4 * c       # the feed-forward and two LayerNorms
    kinds = cfg.kinds
    return {"mamba1": kinds.count("mamba1") * mamba,
            "attention": (kinds.count("swa") + kinds.count("attention"))
            * attention,
            "gmu": kinds.count("gmu") * 2 * c * w,
            "xattn": kinds.count("xattn") * cross,
            "every_layer": cfg.n_layer * every,
            "table": cfg.vocab_size * c, "last_norm": 2 * c}


def rescaled(params, table, last_norm):
    """``params`` with the tied token table times ``table`` and the last
    norm's weight at ``last_norm``: where the benchmark sets the spread of
    its random weights' logits (the configuration's ``embed_init_range`` and
    ``final_norm_init``, with their reasons under ``assumed``)."""
    return dict(params, embed=params["embed"] * table,
                final_norm=params["final_norm"] * last_norm)


class Precision(object):
    """The comparisons of the module comment, fed by the reference's
    ``watch`` a layer and a sequence at a time. The FULL layer's plane is
    kept a sequence (``planes``) for the cross layers after it."""

    LIMITS = (("window_rel_err", WINDOW_LIMIT),
              ("shared_rel_err", SHARED_LIMIT), ("gmu_rel_err", GMU_LIMIT),
              ("state_rel_err", STATE_LIMIT),
              ("stream_rel_err", STREAM_LIMIT))

    def __init__(self, params, cfg):
        self.params, self.cfg = params, cfg
        self.seen = {name: [] for name, _ in self.LIMITS}
        self.planes = {}

    def _stack(self, tree, layer):
        kind = self.cfg.kinds[layer]
        j = self.cfg.kinds[:layer].count(kind)
        return {k: v[j] for k, v in self.params[tree].items()}

    def watch(self, layer, sequence, seen):
        cfg = self.cfg
        kind = cfg.kinds[layer]
        self.seen["stream_rel_err"].append(jamba.stream_error(
            dict({k: v[layer] for k, v in self.params["dense"].items()},
                 ffn_norm=self.params["layers"]["ffn_norm"][layer],
                 ffn_norm_b=self.params["layers"]["ffn_norm_b"][layer]),
            cfg, seen))
        if kind == "mamba1":
            self.seen["state_rel_err"].append(jamba.state_error(cfg, seen))
        elif kind == "swa":
            self.seen["window_rel_err"].extend(
                shared.latent_error(got, seen["mix_out"][at])
                for got, at in program_window(self._stack("swa", layer), cfg,
                                              seen["mix_in"]))
        elif kind == "attention":
            # its own output is the other cells' attention probe's business
            # (the same launchers at ``g = 2``); what is held here is what
            # it LEAVES: the plane its readers attend
            self.planes[sequence] = program_plane(
                self._stack("attn", layer), cfg, seen["mix_in"])
        elif kind == "xattn":
            self.seen["shared_rel_err"].extend(
                shared.latent_error(got, seen["mix_out"][at])
                for got, at in program_cross(
                    self._stack("xattn", layer), cfg, seen["mix_in"],
                    self.planes[sequence]))
        else:
            self.seen["gmu_rel_err"].append(shared.latent_error(
                program_gmu(self._stack("gmu", layer), cfg, seen["mix_in"],
                            seen["memory"]), seen["mix_out"]))

    def readings(self):
        return {name: max(v) if v else None for name, v in self.seen.items()}

    def ok(self):
        r = self.readings()
        return all(r[name] is None or r[name] <= limit
                   for name, limit in self.LIMITS)


@functools.partial(jax.jit, static_argnames=("cfg",))
def program_gmu(weights, cfg, mix_in, memory):
    """What the PROGRAM's gated memory unit adds for the reference's normed
    stream ``mix_in`` [T, C] (cast to the compute type, as the program's own
    norm hands it on) and the reference's ``memory`` [T, W] float32, as
    ``forward`` hands it: [T, C] float32."""
    from deepspeed_tpu.models import decoder

    return decoder.gmu_mix(weights, cfg, mix_in[None].astype(cfg.dtype),
                           memory[None])[0]


def _arenas(cfg, pages):
    from deepspeed_tpu.ops.transformer.kernels.decode_attention import \
        lane_pack

    g = lane_pack(cfg.head_dim, cfg.n_kv)
    return tuple(jnp.zeros((1, pages, -(-cfg.n_kv // g), PROBE_PAGE,
                            g * cfg.head_dim), cfg.dtype) for _ in "kv")


@functools.lru_cache(maxsize=None)
def _mix(cfg, kind, name):
    """``decoder.attention_mix`` of one layer of ``kind`` through the
    program's own ``CacheAttention`` on a paged pool of that one layer, as
    one program: (weights, h [B, S, C], the two arenas, the table (the full
    group's, or the rows' rings), the rows' frontiers) -> (y [B, S, C]
    float32, the arenas)."""
    from deepspeed_tpu.models import decoder, generation

    def run(weights, h, k, v, tbl, pos):
        b = h.shape[0]
        cache = {"pos": pos}
        if kind == "swa":
            # the full group is not touched: a one-page dummy
            dummy = jnp.zeros((1, 1) + k.shape[2:], k.dtype)
            cache.update(k=dummy, v=dummy, wk=k, wv=v, ring_tbl=tbl,
                         block_tbl=jnp.ones((b, 1), jnp.int32))
        else:
            cache.update(k=k, v=v, block_tbl=tbl)
        attend = generation.CacheAttention(cfg, cache, h.shape[1], name)
        planes = attend.wplanes if kind == "swa" else attend.planes
        y, (k, v) = decoder.attention_mix(weights, cfg, h, 0, None, attend,
                                          planes, kind)
        return y.astype(jnp.float32), k, v

    return jax.jit(run, donate_argnums=(2, 3) if kind != "xattn" else ())


def _decode_rows(n, t):
    """One decode position a page, all over the sequence."""
    return np.minimum(np.arange(n) * PROBE_PAGE
                      + (37 * np.arange(n) + 11) % PROBE_PAGE, t - 1)


def _padded(mix_in, cfg):
    t = mix_in.shape[0]
    n_lp = -(-t // PROBE_PAGE)
    return jnp.pad(mix_in, ((0, n_lp * PROBE_PAGE - t), (0, 0))).astype(
        cfg.dtype), n_lp


def program_window(weights, cfg, mix_in):
    """What the PROGRAM's window layer adds to the stream for one sequence
    through A RING as the engine holds one (``ring_pages`` pages a row, page 0
    the trash page): the whole sequence as the LANE serves a prompt, a slice
    of ``PROBE_PAGE`` tokens at a time, so that from the ``n_ring``-th slice
    on every write lands on a page the window has left; and, after each slice
    that a decode position of ``_decode_rows`` follows, ONE DECODE STEP at
    that position on a COPY of the ring as it stands (the slice has written
    the position's page whole: the step rewrites its own key and masks the
    ones past it, as a row admitted into a used ring does):
    [(y [n, C] float32, the positions it stands for)]."""
    from deepspeed_tpu.models import decoder
    from deepspeed_tpu.ops.transformer.kernels.decode_attention import \
        ring_pages

    cfg = decoder.served_config(cfg)
    t = mix_in.shape[0]
    h, n_lp = _padded(mix_in, cfg)
    n_ring = ring_pages(cfg.sliding_window, PROBE_PAGE, PROBE_PAGE)
    k, v = _arenas(cfg, n_ring + 1)
    ring = 1 + jnp.arange(n_ring, dtype=jnp.int32)[None]
    lane, step = _mix(cfg, "swa", "prefill_attn"), _mix(cfg, "swa", None)
    rows = _decode_rows(n_lp, t)
    out, steps = [], []
    for j in range(n_lp):
        y, k, v = lane(weights, h[None, j * PROBE_PAGE:(j + 1) * PROBE_PAGE],
                       k, v, ring, jnp.asarray([j * PROBE_PAGE], jnp.int32))
        out.append(y[0])
        y, _, _ = step(weights, h[rows[j]][None, None], k + 0, v + 0, ring,
                       jnp.asarray(rows[j:j + 1], jnp.int32))
        steps.append(y[0])
    return [(jnp.concatenate(out)[:t], np.arange(t)),
            (jnp.concatenate(steps), rows)]


def program_plane(weights, cfg, mix_in):
    """The shared plane as the PROGRAM's full layer leaves it for one
    sequence: its ``attention_mix`` over the whole sequence a lane slice at a
    time through a paged pool of that one layer: (k, v, the table)."""
    from deepspeed_tpu.models import decoder

    cfg = decoder.served_config(cfg)
    h, n_lp = _padded(mix_in, cfg)
    k, v = _arenas(cfg, n_lp + 1)
    tbl = 1 + jnp.arange(n_lp, dtype=jnp.int32)[None]
    lane = _mix(cfg, "attention", "prefill_attn")
    for j in range(n_lp):
        _, k, v = lane(weights, h[None, j * PROBE_PAGE:(j + 1) * PROBE_PAGE],
                       k, v, tbl, jnp.asarray([j * PROBE_PAGE], jnp.int32))
    return k, v, tbl


def program_cross(weights, cfg, mix_in, plane):
    """What the PROGRAM's cross layer adds for one sequence, reading
    ``plane`` (``program_plane``'s): every lane slice, then one decode step
    of a row a page: [(y [n, C] float32, the positions it stands for)]."""
    from deepspeed_tpu.models import decoder

    cfg = decoder.served_config(cfg)
    k, v, tbl = plane
    t = mix_in.shape[0]
    h, n_lp = _padded(mix_in, cfg)
    lane, step = _mix(cfg, "xattn", "prefill_attn"), _mix(cfg, "xattn", None)
    out = [lane(weights, h[None, j * PROBE_PAGE:(j + 1) * PROBE_PAGE], k, v,
                tbl, jnp.asarray([j * PROBE_PAGE], jnp.int32))[0][0]
           for j in range(n_lp)]
    rows = _decode_rows(n_lp, t)
    y = step(weights, h[rows][:, None], k, v, jnp.tile(tbl, (n_lp, 1)),
             jnp.asarray(rows, jnp.int32))[0]
    return [(jnp.concatenate(out)[:t], np.arange(t)), (y[:, 0], rows)]


def retrace():
    """Drop the compiled probes: a caller that plants another precision in
    the program (``benchmark/probe_phi4flash.py``) has them traced again."""
    _mix.cache_clear()
    program_gmu.clear_cache()
    jamba.retrace()


def published_names(params, cfg):
    """The program's tree under the reference's names. ``layers`` is a
    generator: one layer's slices exist at a time."""
    def layers():
        n = {kind: 0 for kind in set(cfg.kinds)}
        for i, kind in enumerate(cfg.kinds):
            every = {k: v[i] for k, v in params["layers"].items()}
            dense = {k: v[i] for k, v in params["dense"].items()}
            out = {"ln1_w": every["attn_norm"], "ln1_b": every["attn_norm_b"],
                   "ln2_w": every["ffn_norm"], "ln2_b": every["ffn_norm_b"],
                   "gate_up_proj": dense["w_gate_up"],
                   "down_proj": dense["w_down"]}
            tree = "attn" if kind == "attention" else kind
            a = {k: v[n[kind]] for k, v in params[tree].items()}
            if kind == "mamba1":
                out.update({k: a[k] for k in (
                    "in_proj", "conv_w", "conv_b", "x_proj", "dt_proj",
                    "dt_bias", "D", "out_proj")}, A_log=a["A_log"].T)
            elif kind in ("swa", "attention"):
                out.update(qkv_proj=a["wqkv"], qkv_b=a["bqkv"],
                           o_proj=a["wo"], o_b=a["bo"])
            elif kind == "xattn":
                out.update(q_proj=a["wq"], q_b=a["bq"], o_proj=a["wo"],
                           o_b=a["bo"])
            else:
                out.update(gmu_in=a["w_in"], gmu_out=a["w_out"])
            n[kind] += 1
            yield out

    return {"embed_tokens": params["embed"], "layers": layers(),
            "final_ln_w": params["final_norm"],
            "final_ln_b": params["final_norm_b"]}


def hyper(cfg):
    """What the reference is told beside the weights."""
    words = {v: k for k, v in KINDS.items()}
    return {"layer_types": tuple(words[k] for k in cfg.kinds),
            "n_head": cfg.n_head, "n_kv": cfg.n_kv,
            "d_state": cfg.mamba_state, "dt_rank": cfg.mamba_dt_rank,
            "window": cfg.sliding_window, "eps": cfg.rms_norm_eps}


def reference_logits(params, ids, cfg, watch=None):
    """The plain reference on the program's parameter tree, for a
    ``DecoderConfig`` ``cfg`` (the tests call it at a tiny size)."""
    return reference.logits(published_names(params, cfg), ids, hyper(cfg),
                            watch=watch)
