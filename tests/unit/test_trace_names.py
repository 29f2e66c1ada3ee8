"""The names inside the program (PERF.md section 3): one span call that
writes the ring and the profiler's trace under one name, the spans each
engine leaves, a request's instants with its ``rid``, and the regions and
kernel names in the lowered step programs. Nothing here times anything.
"""

import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

import deepspeed_tpu as deepspeed
from deepspeed_tpu.inference import InferenceEngine
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
from deepspeed_tpu.telemetry import NullRecorder, SpanRecorder
from tests.unit.test_chunked_prefill import engine_of, make_model, prompts_of


def _profiled(tmp_path, body):
    """Run ``body`` under a profiler capture; the host events it left as
    ``{name: [stats dict, ...]}`` in time order."""
    with jax.profiler.trace(str(tmp_path)):
        body()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if re.match(r"^(train|inference|request|test)/", ev.name):
                        events.append((ev.start_ns, ev.name,
                                       {str(k): v for k, v in ev.stats}))
    out = {}
    for _, name, stats in sorted(events, key=lambda e: e[0]):
        out.setdefault(name, []).append(stats)
    return out


# ------------------------------------------------------------ one span call


def test_one_call_writes_ring_and_profiler_under_one_name(tmp_path):
    rec = SpanRecorder()

    def body():
        with rec.timed("test/phase", step=3, prefill_tokens=7):
            rec.instant("test/mark", tid=5, rid=7, slot=2)

    seen = _profiled(tmp_path, body)
    # the profiler's trace: same names, the arguments as event stats, the
    # request's track (tid) as one more
    assert seen["test/phase"] == [{"step": 3, "prefill_tokens": 7}]
    assert seen["test/mark"] == [{"tid": 5, "rid": 7, "slot": 2}]
    # the ring: the same two events under the same names
    ring = {e["name"]: e for e in rec.events()}
    assert set(ring) == {"test/phase", "test/mark"}
    assert ring["test/phase"]["ph"] == "X" and \
        ring["test/phase"]["args"] == {"step": 3, "prefill_tokens": 7}
    assert ring["test/mark"]["ph"] == "i" and ring["test/mark"]["tid"] == 5
    assert ring["test/mark"]["args"] == {"rid": 7, "slot": 2}
    assert rec.span_counts() == {"test/phase": 1, "test/mark": 1}


def test_retroactive_span_reaches_the_ring_only(tmp_path):
    rec = SpanRecorder()
    seen = _profiled(tmp_path, lambda: rec.span("test/late", 1.0, 2.0))
    assert "test/late" not in seen
    assert rec.span_counts() == {"test/late": 1}


def test_null_recorder_writes_nowhere(tmp_path):
    rec = NullRecorder()

    def body():
        with rec.timed("test/phase", step=1):
            rec.instant("test/mark", rid=1)

    assert _profiled(tmp_path, body) == {}
    assert rec.events() == [] and rec.span_counts() == {}


# ---------------------------------------------------------- serving engine


STEP_SPANS = ("inference/step", "inference/schedule", "inference/mixed_step",
              "inference/harvest", "inference/deliver")
REQUEST_INSTANTS = ("request/submitted", "request/admitted",
                    "request/first_token", "request/finished")


def test_a_served_request_leaves_its_spans_and_four_instants(tmp_path):
    cfg, model, params = make_model()
    eng = engine_of(model, params)
    eng.generate([prompts_of(cfg, [5])[0]], max_new_tokens=3)  # compile

    def body():
        req = eng.submit(prompts_of(cfg, [11])[0], max_new_tokens=6)
        eng.run()
        body.rid = req.rid

    seen = _profiled(tmp_path, body)
    counts = eng.tracer.span_counts()
    # every phase of a step: one name in the ring and in the trace, each
    # carrying the step's number, children inside inference/step
    steps = len(seen["inference/step"])
    assert steps >= 3
    for name in STEP_SPANS:
        assert len(seen[name]) == steps, name
        assert counts[name] >= steps
        assert [s["step"] for s in seen[name]] == \
            [s["step"] for s in seen["inference/step"]], name
    first = seen["inference/mixed_step"][0]
    assert first["prefill_tokens"] == 8 and first["active_slots"] == 0
    assert seen["inference/mixed_step"][-1]["active_slots"] == 1
    # the request: four instants, one rid, in order, with what it waited
    for name in REQUEST_INSTANTS:
        (stats,) = seen[name]
        assert stats["rid"] == body.rid, name
    assert seen["request/admitted"][0]["queue_ms"] >= 0
    assert seen["request/first_token"][0]["prefill_ms"] > 0
    assert seen["request/finished"][0]["tokens"] == 6
    order = [e["name"] for e in eng.tracer.events()
             if e["name"] in REQUEST_INSTANTS and e["args"]["rid"] == body.rid]
    assert order == list(REQUEST_INSTANTS)
    # the retroactive lifecycle spans stay in the ring for the Chrome export
    for name in ("request/queued", "request/prefill", "request/decode",
                 "request"):
        assert counts[name] >= 1, name


@pytest.mark.parametrize("telemetry", [True, False])
def test_spans_cost_no_compile(telemetry):
    cfg, model, params = make_model()
    eng = engine_of(model, params, telemetry=telemetry)
    for n in (5, 9, 17):
        eng.generate([prompts_of(cfg, [n])[0]], max_new_tokens=4)
    assert eng.compile_count == 1
    assert (eng.tracer.span_counts() != {}) is telemetry


# --------------------------------------------------------- training engine


def _train_engine(**config):
    cfg = GPT2Config.tiny()
    engine, _, _, _ = deepspeed.initialize(
        model=GPT2LMHeadModel(cfg),
        config_params=dict({
            "train_batch_size": 8,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "bf16": {"enabled": True}}, **config))
    ids = np.random.RandomState(0).randint(0, cfg.vocab_size, size=(8, 32))
    return engine, ids


def test_train_batch_leaves_its_spans(tmp_path):
    engine, ids = _train_engine()
    engine.train_batch(batch=(ids, ids))  # compile outside the capture
    seen = _profiled(tmp_path, lambda: [
        engine.train_batch(batch=(ids, ids)) for _ in range(2)])
    assert [s["step_num"] for s in seen["train/step"]] == [1, 2]
    for name in ("train/shard_batch", "train/dispatch", "train/bookkeeping"):
        assert len(seen[name]) == 2, name
    assert engine.tracer.span_counts() == {
        "train/step": 3, "train/shard_batch": 3, "train/dispatch": 3,
        "train/bookkeeping": 3}


def test_three_call_path_leaves_its_spans(tmp_path):
    engine, ids = _train_engine()

    def one():
        loss = engine(ids, ids)
        engine.backward(loss)
        engine.step()

    one()  # compile outside the capture
    seen = _profiled(tmp_path, one)
    for name in ("train/forward", "train/backward", "train/update"):
        assert seen[name] == [{"step_num": 1}], name
    assert "train/step" not in seen


# ------------------------------------------- regions and kernels, lowered


def _op_names(lowered, module):
    """The op_names in the compiled HLO of a lowered program (what a
    trace's embedded HLO holds: a scan body's names joined to its call
    site's), which must be named ``module``."""
    text = lowered.compile().as_text()
    assert text.startswith("HloModule {},".format(module))
    return set(re.findall(r'op_name="([^"]+)"', text))


def _regions(op_names):
    from benchmark import scope_reduce

    names = scope_reduce.scope_names()
    words, kernel_re = set(names["scopes"]), re.compile(names["kernel"])
    regions, kernels = set(), set()
    for op_name in op_names:
        parts = scope_reduce.components(op_name)
        regions.add(scope_reduce.scope_path(parts, words))
        kernels.update(p for p in parts if kernel_re.match(p))
    return regions, kernels


def test_train_step_is_named_and_holds_every_region_and_kernel():
    engine, ids = _train_engine()
    engine.train_batch(batch=(ids, ids))
    (jitted,) = engine._fused_step_cache.values()
    lowered = jitted.lower(
        engine.params, engine.opt_state, (jnp.asarray(ids), jnp.asarray(ids)),
        jax.random.PRNGKey(0), jnp.float32(1e-3), jnp.float32(0.9),
        jnp.float32(0.999))
    op_names = _op_names(lowered, "jit_train_step")
    regions, kernels = _regions(op_names)
    assert {"embed", "block/ln", "block/attn", "block/mlp", "lm_head",
            "optimizer"} <= regions
    assert {"flash_fwd", "flash_bwd_fused"} <= kernels
    # forward AND backward of a region carry its name
    assert any("transpose(jvp(" in n and "/lm_head/" in n for n in op_names)
    assert any("transpose(jvp(" in n and "/block/mlp/" in n
               for n in op_names)


def _lower_mixed(eng):
    i32 = jnp.int32
    return eng._mixed.lower(
        eng._params, eng._adapter, eng.config.chunk_size, eng._spec,
        eng._pool, jnp.zeros((1, eng.config.prefill_chunk), i32), i32(0),
        i32(0), i32(0), jnp.asarray(False), jnp.asarray(False), i32(1),
        i32(-1), jnp.float32(0.0), i32(0), jnp.uint32(0))


def test_mixed_step_is_named_and_holds_every_region_and_kernel():
    cfg, model, params = make_model(n_positions=256)
    # a page of 128 positions is what the paged kernel takes
    eng = InferenceEngine(model, params, config=dict(
        max_slots=2, max_len=256, chunk_size=2, prefill_chunk=16,
        paged_kv=True, kv_page_len=128, use_flash_decode=True))
    op_names = _op_names(_lower_mixed(eng), "jit_mixed_step")
    regions, kernels = _regions(op_names)
    assert {"prefill_lane", "prefill_lane/kv_write", "prefill_lane/attn",
            "decode_scan", "decode_scan/kv_write",
            "decode_scan/attn", "decode_scan/mlp", "decode_scan/lm_head",
            "decode_scan/sample"} <= regions
    # A paged pool whose page is a kernel block forms no view of the arena:
    # the kernels index it, so ``kv_view`` holds no operation here (the
    # dense pool's program below still has one).
    assert "decode_scan/kv_view" not in regions
    # the lane's kernel has a name of its own: in neither old class
    assert kernels == {"prefill_attn", "paged_decode"}
    # The in-place append sits under ``kv_write`` in both lanes, by a name
    # the benchmark's kernel list does not hold yet (it is the benchmark's
    # to add): its time counts under the region.
    from benchmark import scope_reduce
    words = set(scope_reduce.scope_names()["scopes"])
    assert {scope_reduce.scope_path(scope_reduce.components(n), words)
            for n in op_names if "kv_append" in n} == {
        "decode_scan/kv_write", "prefill_lane/kv_write"}
    assert eng.compile_count == 0  # lowering is not a dispatch


def test_contiguous_and_speculative_scan_names():
    cfg, model, params = make_model()
    eng = InferenceEngine(model, params, config=dict(
        max_slots=2, max_len=128, chunk_size=2, prefill_chunk=16,
        use_flash_decode=True, spec_decode=True, spec_k=2, spec_ngram=2))
    regions, kernels = _regions(_op_names(_lower_mixed(eng),
                                          "jit_mixed_step"))
    assert {"decode_scan/draft", "decode_scan/sample",
            "decode_scan/kv_write"} <= regions
    assert kernels == {"prefill_attn", "decode_attn"}


def test_latent_mixed_step_holds_the_latent_regions_and_kernel():
    """Latent attention's step (DeepSeek-V3's block at a tiny size, pages of
    a kernel block): the regions ``mla`` adds inside ``attn``, ``mlp`` for
    the leading dense layer beside ``moe`` for the rest, and exactly two
    kernels by name: the scan's ``latent_decode`` and the lane's call of
    the same body, ``prefill_attn``; the one plane is appended in place
    under ``kv_write`` and no ``kv_view`` is formed."""
    from deepspeed_tpu.models.decoder import DecoderConfig, DecoderLM

    model = DecoderLM(DecoderConfig(
        vocab_size=256, n_layer=2, n_head=4, head_dim=24, hidden_size=64,
        n_positions=512, n_experts=16, experts_per_token=3, expert_width=32,
        rms_norm_eps=1e-6, qk_norm=False, norm_topk_prob=True,
        dtype=jnp.float32, shared_width=32, experts_held=(0, 8),
        kv_lora_rank=32, q_lora_rank=24, qk_nope_dim=16, qk_rope_dim=8,
        v_head_dim=16, rope_yarn=(40.0, 64, 32.0, 1.0, 1.0, 1.0),
        dense_layers=1, dense_width=96, router_scoring="sigmoid", n_group=4,
        topk_group=2, routed_scaling=2.5))
    eng = InferenceEngine(model, model.init(jax.random.PRNGKey(0))["params"],
                          config=dict(max_slots=2, max_len=256, chunk_size=2,
                                      prefill_chunk=16, paged_kv=True,
                                      kv_page_len=128, use_flash_decode=True))
    op_names = _op_names(_lower_mixed(eng), "jit_mixed_step")
    regions, kernels = _regions(op_names)
    assert {"decode_scan/attn/" + w for w in
            ("q_proj", "kv_proj", "rope", "absorb", "o_proj")} <= regions
    assert {"prefill_lane/attn/absorb", "decode_scan/mlp",
            "decode_scan/moe/router", "decode_scan/moe/experts",
            "decode_scan/moe/shared", "decode_scan/kv_write",
            "prefill_lane/kv_write"} <= regions
    assert "decode_scan/kv_view" not in regions
    assert kernels == {"prefill_attn", "latent_decode"}
