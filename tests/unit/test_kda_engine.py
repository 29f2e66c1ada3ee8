"""Kimi Linear's stack served by the engine (``tests/unit/test_kda.py`` has the
mixer, the state's rules and the reference; its sizes and fixtures are this
file's): one program, alone or among neighbours, the kernel path beside the
gather path, a reused slot, the gauges, and the hierarchy's capture and
restore of a latent page set WITH the state a slot.
"""

import jax
import numpy as np
import pytest

from deepspeed_tpu.inference import InferenceEngine, kv_pool
from deepspeed_tpu.inference.kv_hierarchy import offload
from deepspeed_tpu.models import decoder, kda
from deepspeed_tpu.models.decoder import DecoderConfig, DecoderLM
from tests.unit.test_kda import (  # noqa: F401  (``model`` is a fixture)
    CFG, STATE, alone, builder, engine, model, tokens)
from tests.unit.test_telemetry import _parse_prom


# -------------------------------------------------------------- the engine


def test_a_reused_slot_gives_the_stream_it_gives_alone(model):
    first, second = tokens(9, seed=5)[0], tokens(13, seed=6)[0]
    eng = engine(model, max_slots=1)
    a = eng.submit(first, max_new_tokens=7)
    b = eng.submit(second, max_new_tokens=7)
    eng.run()
    assert eng.compile_count == 1 and a.tokens
    # against an engine of its own; no reset from the host
    assert b.tokens == alone(model, second, 7, fresh=True)


def test_the_engine_serves_it_in_one_program_alone_or_among_neighbours(
        model):
    prompts = [tokens(n, seed=30 + n)[0] for n in (5, 20, 9, 12, 7)]
    eng = engine(model)
    reqs = [eng.submit(p, max_new_tokens=10) for p in prompts]
    eng.run()
    assert eng.compile_count == 1 and eng.metrics()["adapter"] == "decoder"
    # three lane slices while neighbours decode, and a late admission
    assert reqs[1].tokens == alone(model, prompts[1], 10)
    assert reqs[4].tokens == alone(model, prompts[4], 10)
    # the reference agrees with every served token (teacher forcing)
    seq = np.concatenate([prompts[1], reqs[1].tokens])[None]
    rows = builder.reference_logits(model[1], seq, CFG)[0][
        len(prompts[1]) - 1:-1]
    assert float(np.max(rows.max(axis=1) - rows[
        np.arange(len(reqs[1].tokens)), reqs[1].tokens])) <= 1e-3


def test_the_kernel_path_serves_what_the_gather_path_serves(model):
    prompts = [tokens(n, seed=40 + n)[0] for n in (5, 13, 9)]
    served = {}
    for flash in (False, True):
        eng = engine(model, use_flash_decode=flash, kv_page_len=128,
                     max_len=256)
        reqs = [eng.submit(p, max_new_tokens=10) for p in prompts]
        eng.run()
        assert eng.compile_count == 1
        served[flash] = [r.tokens for r in reqs]
    assert served[True] == served[False]


def test_the_gauges_read_the_mix_at_three_mla_and_nine_kda_layers():
    """A latent plane as deep as the MLA layers only, and ONE gauge for the
    slots' recurrent state whatever kind of layer carries it."""
    period = ("kda", "kda", "kda", "attention")
    cfg = CFG._replace(n_layer=12, layer_types=period * 3)
    m = DecoderLM(cfg)
    eng = InferenceEngine(m, m.init(jax.random.PRNGKey(0))["params"],
                          config=dict(max_slots=3, max_len=64, chunk_size=4,
                                      prefill_chunk=8, use_flash_decode=False,
                                      paged_kv=True, kv_page_len=8))
    assert eng._pool["k"].shape[0] == 3
    eng._adapter.observe(kv_pool.harvest_snapshot(eng._pool), eng.telemetry)
    from deepspeed_tpu.telemetry.exporters import prometheus_text

    gauges = {}
    for line in prometheus_text(eng.telemetry).splitlines():
        if line.startswith("ds_tpu_") and "expert=" not in line:
            name, value = line.rsplit(" ", 1)
            gauges[name.split("{")[0][len("ds_tpu_"):]] = float(value)
    # 3 layers x one head of 128 stored lanes x 4 bytes (the cell: 3 x 640
    # x 2; the whole model: 7 x 1,280)
    assert gauges["kv_latent_bytes_token"] == 3 * 128 * 4
    assert gauges["ssm_state_bytes"] == 3 * 9 * (4 * 16 * 16 * 4
                                                 + 3 * 192 * 4)
    assert gauges["kv_pool_bytes"] > gauges["ssm_state_bytes"]
    whole = decoder.cache_spec(DecoderConfig(
        vocab_size=8, n_layer=27, n_head=32, head_dim=192, hidden_size=2304,
        n_positions=8, n_experts=8, experts_per_token=1, expert_width=8,
        kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
        layer_types=(period * 7)[:26] + ("attention",), kda_heads=32,
        kda_head_dim=128))
    assert whole.n_layer * whole.n_embd * 2 == 7 * 1280
    assert kv_pool.slot_state_nbytes(whole) == 20 * (32 * 128 * 128 * 4
                                                     + 3 * 12288 * 2)


@pytest.mark.parametrize("name, heads", [
    # a float32 state of whole tiles: all of a row's heads one unit
    ("kda_2_heads_of_128", 2),
    # the tiny configuration: the plain form
    ("kda_4_heads_of_16", 0),
    # no KDA layer at all
    ("no_kda_layer", 0)])
def test_update_unit_heads_gauge_is_the_launchers_own_rule(name, heads):
    """``kda_update_unit_heads`` is Hb as ``kda_update.unit_heads`` resolves
    it for the pool's own ``slot_kda<j>`` (0 where the plain form runs or
    the model has no KDA layer), in ``metrics()`` and the export alike."""
    from deepspeed_tpu.ops.transformer.kernels import kda_update

    cfg = {
        "kda_2_heads_of_128": CFG._replace(
            n_layer=2, layer_types=("kda", "attention"), kda_heads=2,
            kda_head_dim=128),
        "kda_4_heads_of_16": CFG._replace(
            n_layer=2, layer_types=("kda", "attention")),
        "no_kda_layer": CFG._replace(
            n_layer=2, layer_types=None, kda_heads=0, kda_head_dim=0),
    }[name]
    m = DecoderLM(cfg)
    eng = engine((m, m.init(jax.random.PRNGKey(0))["params"]))
    state = eng._pool.get("slot_kda0")
    assert (state is None) == (name == "no_kda_layer")
    if state is not None:
        assert kda_update.unit_heads(state.shape, state.dtype) == heads
    assert eng.metrics()["kda_update_unit_heads"] == heads
    kinds, samples = _parse_prom(eng.prometheus())
    assert kinds["ds_tpu_kda_update_unit_heads"] == "gauge"
    assert [v for (n, _), v in samples.items()
            if n == "ds_tpu_kda_update_unit_heads"] == [heads]


def test_the_engine_serves_through_the_kernel_what_the_plain_form_serves(
        monkeypatch):
    """Two heads of 128 take ``kda_update`` in the decode scan (interpret
    mode here); with the shape rule switched off IN THE TEST the plain form
    serves the same tokens, alone or beside a late neighbour."""
    from deepspeed_tpu.ops.transformer.kernels import kda_update

    cfg = CFG._replace(n_layer=2, layer_types=("kda", "attention"),
                       kda_heads=2, kda_head_dim=128)
    m = DecoderLM(cfg)
    params = m.init(jax.random.PRNGKey(0))["params"]
    prompts = [tokens(n, seed=50 + n)[0] for n in (5, 11)]

    def served():
        eng = engine((m, params), max_slots=2)
        reqs = [eng.submit(p, max_new_tokens=9) for p in prompts]
        eng.run()
        assert eng.compile_count == 1
        return [r.tokens for r in reqs]

    traced = []
    for name, module in (("kernel", kda_update), ("plain", kda)):
        fn = "kda_update" if name == "kernel" else "step_plain"

        def counted(*a, _name=name, _fn=getattr(module, fn), **kw):
            traced.append(_name)
            return _fn(*a, **kw)

        monkeypatch.setattr(module, fn, counted)
    through_kernel = served()
    assert set(traced) == {"kernel"}
    monkeypatch.setattr(kda_update, "supported", lambda shape, dtype: False)
    assert served() == through_kernel and all(through_kernel)
    assert "plain" in traced


def test_preempt_then_resume_continues_token_for_token(model):
    """The hierarchy's capture ships ``slot_*`` with a latent page set."""
    prompts = [tokens(n, seed=20 + n)[0] for n in (6, 9, 5)]
    eng = engine(model, host_offload=True, swap_slots=2)
    reqs = [eng.submit(p, max_new_tokens=20) for p in prompts]
    while not (reqs[0].phase == "decoding" and reqs[0].tokens):
        eng.step()
    assert eng.preempt(reqs[0]) and reqs[0].phase == "swapped"
    record = eng._hier.swap_store.records[reqs[0].rid]
    assert "v" not in record and record["k"].shape[2:] == (1, 8, 128)
    assert record["slot_kda2"].shape == (4, 16, 16)     # the slot's slice
    assert all(np.abs(record["slot_kda{}".format(j)]).max() > 0
               for j in range(3))
    for _ in range(6):
        eng.step()
    eng.release_preempted(reqs[0])
    eng.run()
    assert eng.compile_count == 1
    undisturbed = engine(model, host_offload=True, swap_slots=2)
    same = [undisturbed.submit(p, max_new_tokens=20) for p in prompts]
    undisturbed.run()
    assert [r.tokens for r in reqs] == [r.tokens for r in same]


def test_capture_and_restore_carry_the_plane_and_the_state(model):
    eng = engine(model)
    for n in (6, 9):
        eng.submit(tokens(n, seed=n)[0], max_new_tokens=16)
    eng.step()
    eng.step()
    pool, pager = eng._pool, eng._pager
    pages = pager.row_pages(0)
    rec = offload.capture_slot_paged(pool, 0, pages)
    fresh = pager.alloc_pages(len(pages))
    restored = offload.restore_slot_paged(pool, 2, rec, fresh)
    np.testing.assert_array_equal(
        np.asarray(restored["k"][:, np.asarray(fresh)]),
        np.asarray(pool["k"][:, np.asarray(pages)]))
    for name in STATE:
        np.testing.assert_array_equal(np.asarray(restored[name][2]),
                                      np.asarray(pool[name][0]))
    assert "v" not in rec and not any(k.startswith("aux_") for k in rec)


