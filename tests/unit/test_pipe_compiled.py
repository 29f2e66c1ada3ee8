"""CompiledPipelineEngine: the whole pipeline schedule as ONE XLA program
(runtime/pipe/compiled.py). Parity bar: identical trajectories to the
instruction-interpreter PipelineEngine (which itself is parity-tested
against serial execution, mirroring reference tests/unit/test_pipe.py).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as deepspeed
from deepspeed_tpu.models.simple import DenseOut, DenseRelu, ce_loss
from deepspeed_tpu.pipe import LayerSpec, PipelineModule, TiedLayerSpec


def make_engine(compiled, num_stages=4, gas=2, n_blocks=8, feat=32):
    layers = [LayerSpec(DenseRelu, feat) for _ in range(n_blocks)] + \
        [LayerSpec(DenseOut, 8)]
    model = PipelineModule(layers=layers, num_stages=num_stages,
                           loss_fn=ce_loss, seed_layers=True, base_seed=42,
                           partition_method="uniform", compiled=compiled)
    engine, _, _, _ = deepspeed.initialize(
        model=model,
        config_params={
            "train_batch_size": 8 * gas,
            "gradient_accumulation_steps": gas,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        })
    return engine


def batches(steps, gas, feat=32, seed0=7):
    rng = np.random.RandomState(seed0)
    return [[(rng.randn(8, feat).astype(np.float32),
              rng.randint(0, 8, size=(8,))) for _ in range(gas)]
            for _ in range(steps)]


@pytest.mark.parametrize("num_stages,gas", [(2, 2), (4, 2), (4, 6)])
def test_compiled_matches_interpreter(eight_devices, num_stages, gas):
    """Same layers, same seeds, same data: the one-program engine must
    track the interpreter engine step for step."""
    # Repeat one batch so the loss provably DROPS (random labels are
    # learnable when memorized); parity across engines is the real bar.
    data = batches(1, gas)[0]
    comp = make_engine(True, num_stages=num_stages, gas=gas)
    interp = make_engine(False, num_stages=num_stages, gas=gas)
    lc, li = [], []
    for step in range(4):
        lc.append(comp.train_batch(data_iter=iter(list(data))))
        li.append(interp.train_batch(data_iter=iter(list(data))))
    np.testing.assert_allclose(lc, li, rtol=2e-4, atol=1e-5)
    assert lc[-1] < lc[0]


def test_compiled_transfers_are_collective_permutes(eight_devices):
    """The inter-stage handoff must be a compiled collective (the roll
    across the pipe-sharded slab axis), not host-driven transfers: the
    lowered step program carries a collective-permute, and there is no
    per-instruction Python in the hot loop at all."""
    engine = make_engine(True)
    data = batches(1, 2)[0]
    engine.train_batch(data_iter=iter(list(data)))
    xs = np.stack([d[0] for d in data])[:, :, :]
    ys = np.stack([d[1] for d in data])
    xs = jax.device_put(xs, engine._cp_sharding(
        jax.sharding.PartitionSpec(None, "data")))
    ys = jax.device_put(ys, engine._cp_sharding(
        jax.sharding.PartitionSpec(None, "data")))
    lowered = engine._step_fn.lower(
        engine._cp_params, engine._cp_opt_state, xs, ys,
        jax.random.PRNGKey(0), jnp.float32(1e-2), jnp.float32(0.9),
        jnp.float32(0.999))
    hlo = lowered.compile().as_text()
    assert "collective-permute" in hlo, \
        "inter-stage handoff did not compile to a collective permute"


def test_compiled_checkpoint_interchanges_with_interpreter(eight_devices,
                                                          tmp_path):
    """The compiled engine writes the SAME per-layer checkpoint files as
    the interpreter engine (reference layer-file layout,
    pipe/module.py:536-546) — params saved by one engine load into the
    other and continue with matching losses."""
    data = batches(5, 2)
    comp = make_engine(True)
    for step in range(2):
        comp.train_batch(data_iter=iter(list(data[step])))
    comp.save_checkpoint(str(tmp_path / "ck"))

    # compiled -> interpreter, WITH optimizer state (same per-layer list
    # format on disk).
    interp = make_engine(False)
    interp.train_batch(data_iter=iter(list(data[0])))  # materialize shapes
    interp.load_checkpoint(str(tmp_path / "ck"))
    # fresh compiled engine reloads its own checkpoint too
    comp2 = make_engine(True)
    comp2.train_batch(data_iter=iter(list(data[0])))
    comp2.load_checkpoint(str(tmp_path / "ck"))
    assert comp2.global_steps == 2

    # With params AND moments restored identically, the engines must stay
    # in lockstep for multiple further steps.
    for step in (2, 3):
        li = interp.train_batch(data_iter=iter(list(data[step])))
        lc = comp2.train_batch(data_iter=iter(list(data[step])))
        np.testing.assert_allclose(lc, li, rtol=2e-4, atol=1e-5)

    # interpreter -> compiled direction as well.
    interp.save_checkpoint(str(tmp_path / "ck2"))
    comp3 = make_engine(True)
    comp3.train_batch(data_iter=iter(list(data[0])))
    comp3.load_checkpoint(str(tmp_path / "ck2"))
    li = interp.train_batch(data_iter=iter(list(data[4])))
    lc = comp3.train_batch(data_iter=iter(list(data[4])))
    np.testing.assert_allclose(lc, li, rtol=2e-4, atol=1e-5)


def test_compiled_rejects_tied_and_nonuniform(eight_devices):
    tied = PipelineModule(
        layers=[TiedLayerSpec("emb", DenseRelu, 32),
                LayerSpec(DenseRelu, 32), LayerSpec(DenseRelu, 32),
                TiedLayerSpec("emb", DenseRelu, 32)],
        num_stages=2, loss_fn=ce_loss, compiled=True)
    with pytest.raises(ValueError, match="TiedLayerSpec"):
        deepspeed.initialize(model=tied, config_params={
            "train_batch_size": 8,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-2}}})

    mixed = PipelineModule(
        layers=[LayerSpec(DenseRelu, 32), LayerSpec(DenseRelu, 16),
                LayerSpec(DenseRelu, 64), LayerSpec(DenseOut, 8)],
        num_stages=4, loss_fn=ce_loss, compiled=True)
    with pytest.raises(ValueError, match="identical"):
        deepspeed.initialize(model=mixed, config_params={
            "train_batch_size": 8,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-2}}})


def test_gpt2_pipeline_compiled_matches_untied_interpreter(eight_devices):
    """gpt2_pipeline (models/gpt2.py): embed prologue + uniform blocks +
    final-LN/head epilogue. With the UNTIED head on both engines the
    trajectories must match step for step."""
    from deepspeed_tpu.models.gpt2 import GPT2Config, gpt2_pipeline

    cfg = GPT2Config(vocab_size=256, n_positions=64, n_embd=64, n_layer=4,
                     n_head=4, dropout=0.0, use_flash_attention=False)

    def run(compiled):
        model = gpt2_pipeline(cfg, num_stages=2, tied=False,
                              compiled=compiled)
        engine, _, _, _ = deepspeed.initialize(model=model, config_params={
            "train_batch_size": 8, "gradient_accumulation_steps": 2,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}})
        rng = np.random.RandomState(0)
        ids = rng.randint(0, 256, size=(8, 32))
        micro = [(ids[:4], ids[:4]), (ids[4:], ids[4:])]
        return [engine.train_batch(data_iter=iter(list(micro)))
                for _ in range(3)]

    lc, li = run(True), run(False)
    np.testing.assert_allclose(lc, li, rtol=2e-4, atol=1e-5)
    assert lc[-1] < lc[0]


def test_gpt2_pipeline_tied_interpreter_trains(eight_devices):
    """The tied variant (TiedLayerSpec embedding reused as LM head — the
    reference GPT2ModelPipe shape) runs on the interpreter engine.
    Depth-independent (tying is about the embed/head pair), so 2 layers:
    the multi-block-per-stage path is covered by the untied test above."""
    from deepspeed_tpu.models.gpt2 import GPT2Config, gpt2_pipeline

    cfg = GPT2Config(vocab_size=256, n_positions=64, n_embd=64, n_layer=2,
                     n_head=4, dropout=0.0, use_flash_attention=False)
    model = gpt2_pipeline(cfg, num_stages=2)  # tied by default
    engine, _, _, _ = deepspeed.initialize(model=model, config_params={
        "train_batch_size": 8, "gradient_accumulation_steps": 2,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}})
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 256, size=(8, 32))
    micro = [(ids[:4], ids[:4]), (ids[4:], ids[4:])]
    losses = [engine.train_batch(data_iter=iter(list(micro)))
              for _ in range(3)]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]


def test_compiled_zero_shards_moments_over_data(eight_devices):
    """ZeRO x PP composition on the compiled engine: with
    zero_optimization enabled, the stacked blocks' fp32 moments shard
    over the stage's data replicas (and STAY sharded across steps), while
    the trajectory matches the unsharded run."""
    def run(zero):
        layers = [LayerSpec(DenseRelu, 32) for _ in range(8)] + \
            [LayerSpec(DenseOut, 8)]
        model = PipelineModule(layers=layers, num_stages=2,
                               loss_fn=ce_loss, seed_layers=True,
                               base_seed=42, partition_method="uniform",
                               compiled=True)
        cfg = {
            "train_batch_size": 16,
            "gradient_accumulation_steps": 2,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
            # bf16 in BOTH runs so the only difference is the sharding.
            "bf16": {"enabled": True},
        }
        if zero:
            cfg["zero_optimization"] = {"stage": 1}
        engine, _, _, _ = deepspeed.initialize(model=model,
                                               config_params=cfg)
        data = batches(1, 2)[0]
        losses = [engine.train_batch(data_iter=iter(list(data)))
                  for _ in range(3)]
        return engine, losses

    engine, lz = run(True)
    leaves = jax.tree_util.tree_leaves(
        engine._cp_opt_state["exp_avg"]["blocks"])
    assert any(not l.sharding.is_fully_replicated and
               "data" in str(l.sharding.spec) for l in leaves), \
        [str(l.sharding.spec) for l in leaves]
    assert all(np.isfinite(lz)) and lz[-1] < lz[0]
    # Sharding the moments must not change the math: trajectory parity
    # with the unsharded run.
    _, ld = run(False)
    np.testing.assert_allclose(lz, ld, rtol=2e-4, atol=1e-5)


def test_gpt2_pipeline_compiled_flash_matches_dense(eight_devices):
    """Flash attention runs INSIDE the compiled pipeline (the shard_map
    worker launches raw pallas kernels) and matches the dense-attention
    path numerically."""
    from deepspeed_tpu.models.gpt2 import GPT2Config, gpt2_pipeline

    def run(flash):
        # 2 layers: the parity under test is flash-vs-dense inside one
        # stage's shard_map worker, independent of depth.
        cfg = GPT2Config(vocab_size=256, n_positions=128, n_embd=64,
                         n_layer=2, n_head=4, dropout=0.0,
                         use_flash_attention=flash)
        model = gpt2_pipeline(cfg, num_stages=2, compiled=True)
        engine, _, _, _ = deepspeed.initialize(model=model, config_params={
            "train_batch_size": 8, "gradient_accumulation_steps": 2,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}})
        rng = np.random.RandomState(0)
        ids = rng.randint(0, 256, size=(8, 128))
        micro = [(ids[:4], ids[:4]), (ids[4:], ids[4:])]
        return [engine.train_batch(data_iter=iter(list(micro)))
                for _ in range(3)]

    lf, ld = run(True), run(False)
    np.testing.assert_allclose(lf, ld, rtol=5e-3, atol=1e-3)
    assert lf[-1] < lf[0]


def test_compiled_eval_batch_deterministic_and_matches_interpreter(
        eight_devices, tmp_path):
    """eval_batch on the compiled engine: forward-only one-program
    schedule, deterministic under dropout, and — through a checkpoint
    interchange onto the interpreter engine — numerically equal to the
    interpreter's eval of the same params. 2 layers: the one-program
    eval schedule and checkpoint interchange are depth-independent."""
    from deepspeed_tpu.models.gpt2 import GPT2Config, gpt2_pipeline

    cfg = GPT2Config(vocab_size=256, n_positions=64, n_embd=64, n_layer=2,
                     n_head=4, dropout=0.1, use_flash_attention=False)

    def mk(compiled):
        model = gpt2_pipeline(cfg, num_stages=2, tied=False,
                              compiled=compiled)
        return deepspeed.initialize(model=model, config_params={
            "train_batch_size": 8, "gradient_accumulation_steps": 2,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}})[0]

    comp = mk(True)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 256, size=(8, 32))
    micro = [(ids[:4], ids[:4]), (ids[4:], ids[4:])]
    for _ in range(2):
        comp.train_batch(data_iter=iter(list(micro)))
    e1 = comp.eval_batch(iter(list(micro)))
    e2 = comp.eval_batch(iter(list(micro)))
    assert e1 == e2, "compiled eval not deterministic under dropout"

    comp.save_checkpoint(str(tmp_path / "ck"))
    interp = mk(False)
    interp.train_batch(data_iter=iter(list(micro)))  # materialize
    interp.load_checkpoint(str(tmp_path / "ck"))
    ei = interp.eval_batch(iter(list(micro)))
    np.testing.assert_allclose(e1, ei, rtol=2e-4, atol=1e-5)


def test_compiled_load_checkpoint_before_first_batch(eight_devices, tmp_path):
    """load_checkpoint on a FRESH engine must materialize params and
    moments from the checkpoint files — resuming a run cannot require a
    throwaway train_batch just to allocate state (the warm engine and the
    cold-resumed engine must stay in lockstep afterwards)."""
    data = batches(4, 2)
    warm = make_engine(True)
    for step in range(2):
        warm.train_batch(data_iter=iter(list(data[step])))
    warm.save_checkpoint(str(tmp_path / "ck"))

    cold = make_engine(True)
    cold.load_checkpoint(str(tmp_path / "ck"))  # no prior train_batch
    assert cold.global_steps == 2
    for step in (2, 3):
        lw = warm.train_batch(data_iter=iter(list(data[step])))
        lc = cold.train_batch(data_iter=iter(list(data[step])))
        np.testing.assert_allclose(lc, lw, rtol=2e-4, atol=1e-5)


def test_compiled_load_checkpoint_missing_files_raises(eight_devices,
                                                       tmp_path):
    """A cold engine pointed at a directory without its layer files must
    fail loudly (listing what is missing), not materialize garbage."""
    cold = make_engine(True)
    (tmp_path / "ck" / "global_step0").mkdir(parents=True)
    (tmp_path / "ck" / "latest").write_text("global_step0")
    with pytest.raises(ValueError, match="layer"):
        cold.load_checkpoint(str(tmp_path / "ck"))


def test_compiled_rejects_onebit_adam(eight_devices):
    """OnebitAdam's flat error-feedback buffers don't carry the compiled
    engine's [stage, block] stacking axis — constructing the pair must
    raise at init, not corrupt state at step time."""
    layers = [LayerSpec(DenseRelu, 32) for _ in range(8)] + \
        [LayerSpec(DenseOut, 8)]
    model = PipelineModule(layers=layers, num_stages=4, loss_fn=ce_loss,
                           seed_layers=True, base_seed=42,
                           partition_method="uniform", compiled=True)
    with pytest.raises(ValueError, match="OnebitAdam"):
        deepspeed.initialize(model=model, config_params={
            "train_batch_size": 16,
            "gradient_accumulation_steps": 2,
            "optimizer": {"type": "OneBitAdam",
                          "params": {"lr": 1e-2, "freeze_step": 2}},
        })
