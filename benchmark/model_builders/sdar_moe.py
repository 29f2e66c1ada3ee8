"""Configurations of ``"model_type": "sdar_moe"`` (SDAR-30B-A3B-Chat): the
program's config-driven decoder block (``deepspeed_tpu/models/decoder.py``) at
32 query heads over 4 stored heads of 128, a QK norm a head, top-8 of 128
renormalised SwiGLU experts, and the two fields that say how its tokens are
made (``block_length``, ``mask_token_id``: generation by diffusion over
blocks); its weights from the seed, its plain reference and its account of the
cache. Serving only, by the ``serve_diffusion`` driver, which asks what the
``serve`` driver asks (benchmark/README.md, "What a builder owes") and
``pass_readings``: the reference's readings for every pass of a served request.

WHOSE CHOICE OF EXPERTS THE SERVED TOKENS ARE HELD TO (LFM2's builder found
the need, PERF.md PR 44; the mechanism here is its, written anew for passes).
Every expert is held, and bf16 rounding of the stream moves a router logit by
about a hundredth, so the program keeps another expert than the float32
reference in some layer at most positions; what the layer adds then changes
and no served token could be compared. So the served requests are REPLAYED
through the program's own ``decoder.forward`` over a paged pool (``_replay``:
the kernels the engine's step calls; the prompt, then every pass of every block
in the state the request's record gives it),
``forward`` says which experts it kept (``aux_moe_choice``), and the reference
FOLLOWS them where its own scores leave the choice a near-tie
(``reference._router``: every expert that changed sides within ``FOLLOW_GAP``
router logits of the edge). A change from further away is NOT followed: the
position is held to the reference's own choice and reads over the margin. The
RULE of the choice and its precision are held apart, on identical inputs
(``router_logit_err``: ``decoder.router_logits`` on the reference's own normed
stream against the reference's logits, limit DeepSeek-V3's builder's: a router
matmul in bf16 reads ten times over it). What stays exempt by rule: the served
run is the engine's step, not the replay, and two bf16 executions of one
arithmetic may part at a tie closer than their own difference; a position
whose choice, in some layer of the stream that is compared, stands within
``BAND_GAP`` of the edge is exempt, and the note says how many.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import sdar_moe as reference

# Router logits: bf16 rounding of the stream moves one by 0.006 (first layer)
# to 0.010 (sixth) rms at this width and spread (LFM2's measurement at the same
# hidden size, init range and router; PERF.md PR 44), so 0.08 is 8 of the
# largest, LFM2's ``FOLLOW_SIGMAS``, and 0.003 a quarter of one, its
# ``BAND_SIGMAS``.
FOLLOW_GAP = 0.08
BAND_GAP = 0.003
# ``model_builders/deepseek_v3.py`` ``ROUTER_LIMIT``: the same quantity.
ROUTER_LIMIT = 3e-4
# rows of one ``reference.head`` call: [1024, 151936] float32 is 0.62 GB
HEAD_ROWS = 1024


class Model(object):
    def __init__(self, config):
        from deepspeed_tpu.models.decoder import DecoderConfig, DecoderLM

        if "block_length" not in DecoderConfig._fields:
            raise RuntimeError(
                "this program's decoder block has no block_length: it cannot "
                "build model_type sdar_moe (generation by diffusion over "
                "blocks)")
        for key, published in (("attention_bias", False),
                               ("hidden_act", "silu"), ("rope_scaling", None),
                               ("norm_topk_prob", True),
                               ("decoder_sparse_step", 1),
                               ("mlp_only_layers", []),
                               ("tie_word_embeddings", False)):
            if config[key] != published:
                raise ValueError("model_builders/sdar_moe.py builds {}={!r} "
                                 "only".format(key, published))
        n_head = config["num_attention_heads"]
        self.cfg = DecoderConfig(
            vocab_size=config["vocab_size"],
            n_layer=config["num_hidden_layers"], n_head=n_head,
            head_dim=config["head_dim"], hidden_size=config["hidden_size"],
            n_positions=config["max_position_embeddings"],
            n_experts=config["num_experts"],
            experts_per_token=config["num_experts_per_tok"],
            expert_width=config["moe_intermediate_size"],
            rms_norm_eps=config["rms_norm_eps"],
            rope_theta=float(config["rope_theta"]), qk_norm="head",
            norm_topk_prob=True, tie_word_embeddings=False,
            dtype=jnp.dtype(config["deployment"]["compute_dtype"]),
            initializer_range=config["initializer_range"],
            n_kv_head=config["num_key_value_heads"],
            block_length=config["block_length"],
            mask_token_id=config["mask_token_id"])
        self.module = DecoderLM(self.cfg)
        # the benchmark's own choice of its random weights' scale (the
        # file's ``assumed``): nothing a served model has
        self.final_norm = float(config.get("final_norm_init", 1.0))
        self.n_layer, self.n_head = self.cfg.n_layer, n_head
        self.head_dim = self.cfg.head_dim
        self.vocab_size = self.cfg.vocab_size
        self.block_length = self.cfg.block_length

    def param_shapes(self):
        """The parameter tree's shapes, from shapes alone (no weights)."""
        return jax.eval_shape(lambda: self.module.init(
            jax.random.PRNGKey(0))["params"])

    def sizes(self):
        c = self.cfg
        return {"num_hidden_layers": c.n_layer, "hidden_size": c.hidden_size,
                "heads": c.n_head, "kv_heads": c.n_kv,
                "head_dim": c.head_dim, "num_experts": c.n_experts,
                "num_experts_per_tok": c.experts_per_token,
                "moe_intermediate_size": c.expert_width,
                "vocab_size": c.vocab_size, "block_length": c.block_length,
                "mask_token_id": c.mask_token_id,
                "params": sum(int(np.prod(leaf.shape)) for leaf in
                              jax.tree.leaves(self.param_shapes()))}

    def init_params(self, seed, on_host=False):
        """Random weights from the seed in the type they are served in, made
        in one jitted program on the default device; the last norm's weight
        at ``final_norm_init``. The seed is an argument of that program, so
        that one cached program serves every seed."""
        def make(key):
            params = self.module.init(key)["params"]
            return dict(params,
                        final_norm=params["final_norm"] * self.final_norm)

        return jax.jit(make)(jax.random.PRNGKey(seed))

    def kv_bytes_per_token_layer(self):
        """A key and a value for every STORED head, in the type the engine
        stores."""
        return 2 * self.cfg.n_kv * self.head_dim * self.cfg.dtype.itemsize

    def reference_logits(self, params, ids):
        """[B, T] tokens -> [B, T, V] float32 logits of the plain reference
        under the block visibility rule, read AT each position (no pass of
        this model's generation is a next-token step: the driver holds the
        served tokens by ``pass_readings``)."""
        tree = published_names(params, self.cfg)
        return jnp.stack([reference.logits(
            tree, row, self.cfg.mask_token_id, **hyper(self.cfg))
            for row in np.asarray(ids)])

    def pass_readings(self, params, served, width, page_len):
        return pass_readings(params, self.cfg, served, width, page_len)


def published_names(params, cfg):
    """The program's tree under the reference's (the published) names.
    ``layers()`` is a generator: one layer's slices exist at a time."""
    q, kv = cfg.n_embd, cfg.n_kv * cfg.head_dim
    f = cfg.expert_width

    def layers():
        for i in range(cfg.n_layer):
            p = {k: v[i] for k, v in params["layers"].items()}
            yield {"input_layernorm": p["attn_norm"],
                   "q_proj": p["wqkv"][:, :q],
                   "k_proj": p["wqkv"][:, q:q + kv],
                   "v_proj": p["wqkv"][:, q + kv:],
                   "q_norm": p["q_norm"], "k_norm": p["k_norm"],
                   "o_proj": p["wo"],
                   "post_attention_layernorm": p["ffn_norm"],
                   "gate": p["router"],
                   "gate_proj": p["w_gate_up"][..., :f],
                   "up_proj": p["w_gate_up"][..., f:],
                   "down_proj": p["w_down"]}

    return {"embed_tokens": params["embed"], "layers": layers,
            "norm": params["final_norm"], "lm_head": params["lm_head"]}


def hyper(cfg, follow_gap=0.0):
    return {"n_head": cfg.n_head, "n_kv_head": cfg.n_kv,
            "top_k": cfg.experts_per_token, "eps": cfg.rms_norm_eps,
            "theta": cfg.rope_theta, "block_length": cfg.block_length,
            "follow_gap": follow_gap}


# ----------------------------------------------------------- the served record

def states_of(prompt, tokens, passes, length, steps):
    """What a served request's record says every pass of every generated
    block ran on. -> (first: the first generated block's first position;
    ids [NB, L] the blocks' final tokens; when [NB, L] the pass a position
    was unmasked in, -1 for a token of the prompt, ``steps`` for a position
    past the request's end, of which the record holds nothing)."""
    prompt = np.asarray(prompt)
    p, n = len(prompt), len(tokens)
    first = p // length * length
    n_blocks = -(-(p + n - first) // length)
    ids = np.zeros((n_blocks * length,), np.int32)
    when = np.full((n_blocks * length,), steps, np.int32)
    ids[:p - first], when[:p - first] = prompt[first:], -1
    ids[p - first:p - first + n] = tokens
    when[p - first:p - first + n] = passes
    return first, ids.reshape(n_blocks, length), \
        when.reshape(n_blocks, length)


@functools.partial(jax.jit, static_argnames=("cfg", "max_len", "steps",
                                             "page_len"))
def _replay(params, cfg, prompts, first, ids, when, max_len, steps, page_len):
    """The program's own passes over ``R`` served requests, the record's
    tokens forced: prompts [R, lane] (whole blocks of each prompt, padded),
    first [R], ids / when [R, NB, L] (``states_of``). -> (the experts
    ``forward`` kept for the prompt's positions [layers, R, lane, k], for
    every pass of every block [NB, steps + 1, layers, R, L, k]: pass
    ``steps`` is the commit pass, the clean tokens).

    Over a PAGED pool of the cell's page, a row its own pages, configured as
    the engine's ``bind`` configures the served model: the passes go through
    the kernels the engine's step calls (``kv_append``, ``prefill_attn``,
    ``paged_decode`` at ``rep x block`` rows; the gather and the einsum
    where a page is no kernel block), so that what parts the replay from the
    served run is the batch beside a row, not another softmax. (Over a dense
    cache and the einsum the two parted at router gaps past ``BAND_GAP``: 3
    runs of 20 read margins of 0.063-0.080, each the size of one expert
    changed; my chip runs, PR 51.)"""
    from deepspeed_tpu.inference import kv_pool
    from deepspeed_tpu.models import decoder

    cfg = decoder.served_config(cfg)._replace(kv_page_len=page_len)
    r = prompts.shape[0]
    asks = {"aux_moe_choice": jnp.zeros((), jnp.int32)}
    pool = kv_pool.init_pool(decoder.cache_spec(cfg), r, max_len,
                             page_len=page_len)
    pages = pool["block_tbl"].size
    cache = dict(k=pool["k"], v=pool["v"], pos=pool["pos"], block_tbl=(
        1 + jnp.arange(pages, dtype=jnp.int32)).reshape(
            pool["block_tbl"].shape), **asks)
    # a prompt's pad columns write keys past ``first``: the first block's
    # passes write over them before any query sees them
    _, cache = decoder.forward(params, cfg, prompts, cache,
                               attn_name="prefill_attn")
    lane_choice = cache.pop("aux_moe_choice")
    cache = dict(cache, pos=first)

    def block(cache, x):
        tok, at = x
        kept = []
        for k in range(steps + 1):
            _, after = decoder.forward(
                params, cfg, jnp.where(at >= k, cfg.mask_token_id, tok),
                dict(cache, **asks))
            kept.append(after.pop("aux_moe_choice"))
            cache = dict(after, pos=cache["pos"])
        return dict(cache, pos=cache["pos"] + cfg.block_length), \
            jnp.stack(kept)

    _, choices = jax.lax.scan(
        block, cache, (ids.transpose(1, 0, 2), when.transpose(1, 0, 2)))
    return lane_choice, choices


def program_choices(params, cfg, served, width, steps, page_len):
    """The experts the program keeps, for the clean stream and for the noisy
    copy before each denoising pass, of every served request (``served``: a
    list of (prompt, tokens, passes)): [R, 1 + steps, layers, width, k],
    padded with expert 0 past a request's positions."""
    length = cfg.block_length
    states = [states_of(p, t, w, length, steps) for p, t, w in served]
    lane = -(-max(max(s[0] for s in states), length) // length) * length
    n_blocks = max(s[1].shape[0] for s in states)
    prompts = np.zeros((len(served), lane), np.int32)
    ids = np.zeros((len(served), n_blocks, length), np.int32)
    when = np.full((len(served), n_blocks, length), steps, np.int32)
    for r, ((prompt, _, _), (first, tok, at)) in enumerate(
            zip(served, states)):
        prompts[r, :first] = np.asarray(prompt)[:first]
        ids[r, :len(tok)], when[r, :len(at)] = tok, at
    first = np.asarray([s[0] for s in states], np.int32)
    lane_choice, choices = _replay(
        params, cfg, jnp.asarray(prompts), jnp.asarray(first),
        jnp.asarray(ids), jnp.asarray(when), max_len=int(width),
        steps=int(steps), page_len=int(page_len))
    lane_choice, choices = np.asarray(lane_choice), np.asarray(choices)
    k = choices.shape[-1]
    out = np.zeros((len(served), 1 + steps, cfg.n_layer, width, k), np.int32)
    for r, (start, tok, _) in enumerate(states):
        out[r, :, :, :start] = lane_choice[:, r, :start][None]
        # [NB, pass, layers, L, k] -> [pass, layers, NB * L, k]
        mine = choices[:len(tok), :, :, r].transpose(1, 2, 0, 3, 4).reshape(
            steps + 1, cfg.n_layer, -1, k)
        stop = start + mine.shape[2]
        out[r, 0, :, start:stop] = mine[steps]          # the clean stream
        out[r, 1:, :, start:stop] = mine[:steps]
    return out, states


@functools.partial(jax.jit, static_argnames=("mask_id",))
def _row_readings(logits, token, mask_id):
    """Of float32 logits [N, V] (the mask id left out, as the program leaves
    it): how far the row's largest stands over ``token``'s, and the log of
    the confidence, softmax's largest."""
    logits = jnp.where(jnp.arange(logits.shape[-1]) == mask_id, -jnp.inf,
                       logits)
    top = jnp.max(logits, axis=-1)
    picked = jnp.take_along_axis(logits, token[:, None], axis=1)[:, 0]
    return top - picked, -jnp.log(jnp.sum(jnp.exp(logits - top[:, None]),
                                          axis=-1))


def program_router_logits(router, ffn_in):
    from deepspeed_tpu.models import decoder

    return decoder.router_logits(ffn_in, router)


def pass_readings(params, cfg, served, width, page_len):
    """The reference's readings for every denoising pass of the served
    requests ``served`` (a list of (prompt, tokens, passes, steps)), each
    sequence padded to ``width`` so that one compiled reference serves every
    run; ``page_len``: the engine's page, for the replay's pool. -> a list, a request, of dicts of [NB, L] arrays over its generated
    blocks: ``when`` (``states_of``), ``margin`` (by how many logits the
    reference, run on the state BEFORE the pass a position was unmasked in,
    prefers its own argmax there to the served token), ``confidence`` (the
    log of the reference's confidence at every position masked before
    denoising pass k: [steps, NB, L]), ``exempt`` [steps, NB, L] (``BAND_GAP``);
    and a dict of what the comparison rests on."""
    length, mask_id = cfg.block_length, cfg.mask_token_id
    steps = served[0][3]
    assert all(s[3] == steps for s in served), "one step count a check"
    tree = published_names(params, cfg)
    choices, states = program_choices(
        params, cfg, [s[:3] for s in served], width, steps, page_len)
    sizes = hyper(cfg, FOLLOW_GAP)
    out, routing = [], {"differ": 0, "followed": 0, "not_followed": 0,
                        "furthest_followed": 0.0, "furthest_miss": None,
                        "router_logit_err": 0.0}
    for r, ((prompt, tokens, _, _), (first, tok, when)) in enumerate(
            zip(served, states)):
        stop = first + tok.size
        clean = np.zeros((width,), np.int32)
        clean[:first], clean[first:stop] = np.asarray(prompt)[:first], \
            tok.reshape(-1)
        at = np.full((width,), -1, np.int32)
        at[first:stop] = when.reshape(-1)
        # positions past the request's end (a last block cut short) hold
        # tokens the record does not: nothing of them is counted
        held = np.zeros((width,), bool)
        held[:len(prompt) + len(tokens)] = True
        near = np.zeros((1 + steps, width), bool)

        def watch(layer, stream, seen, tree_layer):
            differ = np.asarray(seen["differ"]) & held
            followed = np.asarray(seen["followed"]) & held
            far = np.asarray(seen["far"])
            routing["differ"] += int(differ.sum())
            routing["followed"] += int(followed.sum())
            missed = differ & ~followed
            routing["not_followed"] += int(missed.sum())
            if followed.any():
                routing["furthest_followed"] = max(
                    routing["furthest_followed"], float(far[followed].max()))
            if missed.any():
                routing["furthest_miss"] = max(
                    routing["furthest_miss"] or 0.0, float(far[missed].max()))
            near[stream] |= np.asarray(seen["gap"]) < BAND_GAP
            if stream == 0 and r == 0:
                # the router on identical inputs: the reference's normed
                # stream through the program's own function
                got = program_router_logits(tree_layer["gate"],
                                            seen["ffn_in"])
                with jax.default_matmul_precision("highest"):
                    want = jnp.matmul(
                        seen["ffn_in"],
                        jnp.asarray(tree_layer["gate"], jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
                routing["router_logit_err"] = max(
                    routing["router_logit_err"],
                    float(jnp.max(jnp.abs(got - want)[:stop])))

        noisy = [(clean, at >= k) for k in range(steps)]
        _, rows = reference.noisy_hidden(
            tree, clean, noisy, mask_id, follow=choices[r], watch=watch,
            **sizes)
        margin = np.zeros((width,), np.float32)
        confidence = np.full((steps, width), -np.inf, np.float32)
        for k, x in enumerate(rows):
            where = np.flatnonzero((at >= k) & held)
            for a in range(0, len(where), HEAD_ROWS):
                idx = where[a:a + HEAD_ROWS]
                pad = np.resize(idx, HEAD_ROWS)   # one compiled shape
                gap, conf = _row_readings(
                    reference.head(x[pad], tree["norm"], tree["lm_head"],
                                   cfg.rms_norm_eps),
                    jnp.asarray(clean[pad]), mask_id)
                gap, conf = np.asarray(gap)[:len(idx)], \
                    np.asarray(conf)[:len(idx)]
                confidence[k, idx] = conf
                chosen = at[idx] == k
                margin[idx[chosen]] = gap[chosen]
        shape = when.shape
        out.append({
            "when": when, "first": first,
            "margin": margin[first:stop].reshape(shape),
            "confidence": confidence[:, first:stop].reshape((steps,) + shape),
            "exempt": near[1:, first:stop].reshape((steps,) + shape)})
    routing.update(follow_gap=FOLLOW_GAP, band_gap=BAND_GAP,
                   router_limit=ROUTER_LIMIT)
    return out, routing
