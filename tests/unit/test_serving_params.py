"""The serving engine holds its weights in the type the step computes in
(``ModelAdapter.serving_params``, ``generation.serving_params``).

A float32 GPT-2 tree served in bf16 used to be cast at every use inside the
step; XLA hoists those converts out of the decode scan, so the WHOLE tree was
converted once a step. The engine now casts once, when it is built. What is
held here, on the CPU at a tiny GPT-2:

(a) which leaves the engine holds in which type, and that the caller's tree is
    left whole;
(b) the streams are those of the engine that casts inside the step (the
    parent's program) and of ``generation.generate`` on the float32 tree;
(c) the compiled ``mixed_step`` converts no weight-shaped float32 array;
(d) ``DecoderAdapter`` keeps the protocol's default, and the cast is idempotent;
(e) gather-then-cast is cast-then-gather, bit for bit;
(f) the ``setup/params`` span and the gauge say what was cast.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu import telemetry
from deepspeed_tpu.inference import InferenceConfig, InferenceEngine
from deepspeed_tpu.inference.adapters import DecoderAdapter, GPT2Adapter
from deepspeed_tpu.models import generation
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
from tests.unit.test_trace_names import _lower_mixed

BF16, F32 = jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)
# odd widths, so that no two kinds of weight share a shape: (c) reads shapes
EMBD, LAYERS, VOCAB, POSITIONS = 48, 2, 160, 96

_MODEL = []


def tiny():
    """(config, model, the float32 tree flax makes) of a bf16 GPT-2."""
    if not _MODEL:
        cfg = GPT2Config(
            vocab_size=VOCAB, n_positions=POSITIONS, n_embd=EMBD,
            n_layer=LAYERS, n_head=4, dropout=0.0,
            use_flash_attention=False, dtype=jnp.bfloat16)
        model = GPT2LMHeadModel(cfg)
        params = jax.jit(model.init)(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
        _MODEL.append((cfg, model, params))
    return _MODEL[0]


def paths(tree):
    return {"/".join(str(k.key) for k in path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def cast_here(path):
    return path == "wpe" or path.rsplit("/", 1)[-1] in ("kernel", "bias") \
        and "ln_" not in path


def engine(params, **kw):
    cfg, model, _ = tiny()
    conf = dict(max_slots=2, max_len=64, chunk_size=4, prefill_chunk=8,
                use_flash_decode=False)
    conf.update(kw)
    return InferenceEngine(model, params, config=InferenceConfig(**conf))


# ------------------------------------------------------------------ (a)

def test_engine_holds_what_the_step_reads_and_leaves_the_callers_tree():
    _, _, params = tiny()
    before = {p: (leaf.dtype, leaf.unsafe_buffer_pointer())
              for p, leaf in paths(params).items()}
    held = paths(engine(params)._params)
    assert set(held) == set(before)
    for path, leaf in held.items():
        assert leaf.dtype == (BF16 if cast_here(path) else F32), path
        assert leaf.shape == paths(params)[path].shape
    assert held["wte"].dtype == F32 and held["wpe"].dtype == BF16
    assert sum(cast_here(p) for p in held) == 8 * LAYERS + 1
    # the caller's tree: the same types, in the same buffers (no donation)
    after = {p: (leaf.dtype, leaf.unsafe_buffer_pointer())
             for p, leaf in paths(params).items()}
    assert after == before
    # what the step does NOT cast is the caller's own array, not a copy
    for path, leaf in held.items():
        assert (leaf is paths(params)[path]) == (not cast_here(path)), path


# ------------------------------------------------------------------ (b)

def _streams(eng, cfg):
    rng = np.random.RandomState(11)
    reqs = []
    for i, n in enumerate((5, 11, 3, 9)):
        prompt = rng.randint(0, cfg.vocab_size, size=(n,)).astype(np.int32)
        sampled = i % 2 == 1
        reqs.append(eng.submit(
            prompt, max_new_tokens=10,
            temperature=0.8 if sampled else 0.0,
            top_k=12 if sampled else None, seed=100 + i))
    eng.run()
    assert eng.compile_count == 1
    return [list(r.tokens) for r in reqs]


@pytest.mark.parametrize("spec", [False, True], ids=["plain", "spec"])
@pytest.mark.parametrize("paged", [True, False], ids=["paged", "dense"])
def test_streams_are_those_of_the_step_that_casts_at_every_use(
        paged, spec, monkeypatch):
    """Greedy and sampled requests side by side: the engine built from the
    float32 tree, the engine handed the tree already cast, and the engine
    that keeps the float32 tree and casts inside the step (the program
    before this change) give the same tokens."""
    cfg, _, params = tiny()
    kw = dict(paged_kv=paged, kv_page_len=8)
    if spec:
        kw.update(spec_decode=True, spec_k=2, spec_ngram=2)
    mine = engine(params, **kw)
    assert mine.metrics()["params_cast_bytes"] > 0
    cast = engine(mine._params, **kw)
    assert cast.metrics()["params_cast_bytes"] == 0
    monkeypatch.setattr(GPT2Adapter, "serving_params",
                        lambda self, tree: tree)
    old = engine(params, **kw)
    assert old._params is params
    want = _streams(old, cfg)
    assert _streams(mine, cfg) == want
    assert _streams(cast, cfg) == want
    assert len({tuple(s) for s in want}) == len(want)


@pytest.mark.parametrize("temperature", [0.0, 0.9], ids=["greedy", "sampled"])
def test_generate_on_the_float32_tree_is_generate_on_the_cast_tree(
        temperature):
    cfg, model, params = tiny()
    prompt = np.random.RandomState(5).randint(
        0, cfg.vocab_size, size=(2, 9)).astype(np.int32)
    cast = generation.serving_params(params, generation.as_gencfg(cfg))
    out = [np.asarray(generation.generate(
        model, tree, prompt, 12, temperature=temperature, top_k=20,
        rng=jax.random.PRNGKey(3))) for tree in (params, cast)]
    np.testing.assert_array_equal(out[0], out[1])


def test_greedy_engine_stream_is_generates_on_the_float32_tree():
    """The served tokens of the engine that cast its tree against
    ``generate`` on the tree as it came, in float32 compute (bf16 sums differ
    between the engine's sliced prefill and ``generate``'s whole one; the
    cast itself is held above): a float32 model casts nothing and must
    still serve."""
    cfg = GPT2Config(vocab_size=VOCAB, n_positions=POSITIONS, n_embd=EMBD,
                     n_layer=LAYERS, n_head=4, dropout=0.0,
                     use_flash_attention=False, dtype=jnp.float32)
    model = GPT2LMHeadModel(cfg)
    params = tiny()[2]
    eng = InferenceEngine(model, params, config=InferenceConfig(
        max_slots=2, max_len=64, chunk_size=4, prefill_chunk=8,
        use_flash_decode=False))
    assert eng._params is params
    assert eng.metrics()["params_cast_bytes"] == 0
    prompt = np.random.RandomState(7).randint(
        0, cfg.vocab_size, size=(7,)).astype(np.int32)
    req = eng.submit(prompt, max_new_tokens=8)
    eng.run()
    want = np.asarray(generation.generate(
        model, params, prompt[None], 8, temperature=0.0))[0].tolist()
    assert list(req.tokens) == want


# ------------------------------------------------------------------ (c)

def _weight_converts(text, shapes):
    """Lines of compiled HLO that convert a weight to bf16: a ``convert``
    instruction, alone or inside a fusion, whose result is ``bf16[<a weight's
    shape>]`` (the text names operands without their types; a convert's
    operand has its result's shape, and no activation of this program has a
    weight's)."""
    found = re.findall(
        r"^\s*(?:ROOT )?%[\w.-]+ = bf16\[([\d,]+)\]\S* convert\(.*$", text,
        re.M)
    return [shape for shape in found if shape in shapes]


@pytest.mark.parametrize("fed", ["float32", "uncast"])
def test_compiled_mixed_step_converts_no_weight(fed, monkeypatch):
    """The compiled step of the float32-fed engine has no convert of a
    float32 array of a weight's shape; the step of an engine that keeps the
    float32 tree (``uncast``: the reader's own check) has one a weight."""
    _, _, params = tiny()
    weights = {",".join(map(str, leaf.shape))
               for path, leaf in paths(params).items()
               if leaf.ndim == 2 and path != "wte"}
    assert len(weights) == 5        # wpe and the four Dense kernels
    if fed == "uncast":
        monkeypatch.setattr(GPT2Adapter, "serving_params",
                            lambda self, tree: tree)
    hits = _weight_converts(_lower_mixed(engine(
        params, paged_kv=True, kv_page_len=8)).compile().as_text(), weights)
    if fed == "uncast":
        # the four kernels a layer, in the lane's branch and in the scan
        assert sorted(set(hits)) == sorted(weights - {"96,48"})
        assert len(hits) >= 4 * LAYERS
    else:
        assert hits == []


# ------------------------------------------------------------------ (d)

def test_decoder_adapter_returns_its_argument():
    from deepspeed_tpu.models.decoder import DecoderConfig

    adapter = DecoderAdapter.from_model(DecoderConfig(
        vocab_size=64, n_layer=1, n_head=2, head_dim=8, hidden_size=16,
        n_positions=32, n_experts=2, experts_per_token=1, expert_width=16,
        dtype=jnp.bfloat16), use_flash_decode=False)
    tree = {"w": jnp.ones((4, 4), jnp.float32)}
    assert adapter.serving_params(tree) is tree


def test_a_tree_cast_twice_is_the_same_objects():
    cfg, _, params = tiny()
    adapter = GPT2Adapter.from_model(cfg, use_flash_decode=False)
    once = adapter.serving_params(params)
    twice = adapter.serving_params(once)
    assert jax.tree_util.tree_structure(once) == \
        jax.tree_util.tree_structure(twice) == \
        jax.tree_util.tree_structure(params)
    for a, b in zip(jax.tree_util.tree_leaves(once),
                    jax.tree_util.tree_leaves(twice)):
        assert a is b
    # and the values are the eager cast's
    for path, leaf in paths(once).items():
        want = paths(params)[path]
        np.testing.assert_array_equal(
            np.asarray(leaf.astype(F32)),
            np.asarray(want.astype(leaf.dtype).astype(F32)))


# ------------------------------------------------------------------ (e)

@pytest.mark.parametrize("table", ["wte", "wpe"])
def test_gather_then_cast_is_cast_then_gather(table):
    _, _, params = tiny()
    w = params[table] * 37.0        # off the initializer's small values
    ids = jnp.asarray(np.random.RandomState(2).randint(
        0, w.shape[0], size=(3, 17)))
    a = jax.jit(lambda w, i: w[i].astype(jnp.bfloat16))(w, ids)
    b = jax.jit(lambda w, i: w.astype(jnp.bfloat16)[i])(w, ids)
    assert a.dtype == b.dtype == BF16
    np.testing.assert_array_equal(
        np.asarray(a).view(np.uint16), np.asarray(b).view(np.uint16))


# ------------------------------------------------------------------ (f)

@pytest.mark.parametrize("given", ["float32", "bf16"])
def test_the_span_and_the_gauge_say_what_was_cast(given):
    _, _, params = tiny()
    cfg = tiny()[0]
    if given == "bf16":
        params = generation.serving_params(params, generation.as_gencfg(cfg))
    rec = telemetry.process_recorder()
    n0 = rec.span_counts().get("setup/params", 0)
    eng = engine(params)
    spans = [ev for ev in rec.events() if ev["name"] == "setup/params"]
    assert rec.span_counts()["setup/params"] == n0 + 1
    args = spans[-1]["args"]
    want_leaves = 8 * LAYERS + 1 if given == "float32" else 0
    want_bytes = sum(
        4 * leaf.size for path, leaf in paths(tiny()[2]).items()
        if cast_here(path)) if given == "float32" else 0
    assert args == {"cast_leaves": want_leaves, "cast_bytes": want_bytes}
    assert eng.metrics()["params_cast_bytes"] == want_bytes
    assert eng.telemetry.gauge("params_cast_bytes").value == want_bytes
    assert "params_cast_bytes" in eng.prometheus()
