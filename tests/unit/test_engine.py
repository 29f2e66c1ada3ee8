"""End-to-end engine tests on the virtual CPU mesh: the DeepSpeed training
loop (`loss = engine(x, y); engine.backward(loss); engine.step()`) against
SimpleModel, mirroring reference tests/unit/test_fp16.py / test_zero.py basics."""

import jax
import numpy as np
import pytest

import deepspeed_tpu as deepspeed
from deepspeed_tpu.models.simple import SimpleModel
from deepspeed_tpu.parallel import mesh as mesh_lib


def base_config(**extra):
    cfg = {
        "train_batch_size": 8,
        "steps_per_print": 100,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
    }
    cfg.update(extra)
    return cfg


def random_batch(batch=8, dim=16, classes=16, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(batch, dim).astype(np.float32)
    y = rng.randint(0, classes, size=(batch,))
    return x, y


def run_steps(engine, steps=10, dim=16):
    losses = []
    for i in range(steps):
        x, y = random_batch(batch=engine.train_batch_size() //
                            engine.gradient_accumulation_steps(),
                            dim=dim, seed=i % 3)
        loss = engine(x, y)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    return losses


def test_fp32_loss_decreases():
    model = SimpleModel(hidden_dim=16)
    engine, optimizer, _, _ = deepspeed.initialize(
        model=model, config_params=base_config())
    losses = run_steps(engine, steps=20)
    assert losses[-1] < losses[0]


def test_bf16_loss_decreases():
    model = SimpleModel(hidden_dim=16)
    engine, _, _, _ = deepspeed.initialize(
        model=model, config_params=base_config(bf16={"enabled": True}))
    losses = run_steps(engine, steps=20)
    assert losses[-1] < losses[0]


def test_amp_maps_to_bf16_policy():
    """`amp: {enabled: true}` is the reference's apex hook (engine.py:
    569-575); here it maps to the bf16 mixed-precision cast policy."""
    import jax.numpy as jnp
    model = SimpleModel(hidden_dim=16)
    engine, _, _, _ = deepspeed.initialize(
        model=model, config_params=base_config(amp={"enabled": True}))
    assert engine.compute_dtype == jnp.bfloat16
    assert engine.loss_scaler is None  # bf16 policy needs no scaling
    losses = run_steps(engine, steps=20)
    assert losses[-1] < losses[0]


def test_amp_opt_level_o0_stays_fp32():
    import jax.numpy as jnp
    model = SimpleModel(hidden_dim=16)
    engine, _, _, _ = deepspeed.initialize(
        model=model,
        config_params=base_config(amp={"enabled": True, "opt_level": "O0"}))
    assert engine.compute_dtype == jnp.float32


def test_amp_exclusive_with_fp16():
    model = SimpleModel(hidden_dim=16)
    with pytest.raises(ValueError, match="mutually exclusive"):
        deepspeed.initialize(
            model=model,
            config_params=base_config(amp={"enabled": True},
                                      fp16={"enabled": True}))


def test_fp16_loss_scaling_runs():
    model = SimpleModel(hidden_dim=16)
    engine, _, _, _ = deepspeed.initialize(
        model=model,
        config_params=base_config(fp16={"enabled": True,
                                        "initial_scale_power": 8}))
    losses = run_steps(engine, steps=10)
    assert losses[-1] < losses[0]
    assert engine.loss_scaler is not None


def test_gradient_accumulation_boundary():
    model = SimpleModel(hidden_dim=16)
    engine, _, _, _ = deepspeed.initialize(
        model=model,
        config_params=base_config(train_batch_size=32 * mesh_lib.dp_size(
            mesh_lib.build_mesh()),
                                  gradient_accumulation_steps=4))
    assert engine.gradient_accumulation_steps() == 4
    steps_before = engine.global_steps
    for i in range(8):
        x, y = random_batch(batch=engine.train_micro_batch_size_per_gpu() *
                            engine.dp_world_size, seed=i)
        loss = engine(x, y)
        engine.backward(loss)
        engine.step()
    # 8 micro steps at gas=4 → exactly 2 optimizer steps
    assert engine.global_steps == steps_before + 2


def test_gradient_clipping_runs():
    model = SimpleModel(hidden_dim=16)
    engine, _, _, _ = deepspeed.initialize(
        model=model, config_params=base_config(gradient_clipping=1.0))
    losses = run_steps(engine, steps=5)
    assert np.isfinite(losses).all()


def test_lamb_optimizer():
    model = SimpleModel(hidden_dim=16)
    engine, _, _, _ = deepspeed.initialize(
        model=model,
        config_params=base_config(
            optimizer={"type": "Lamb", "params": {"lr": 1e-2}}))
    losses = run_steps(engine, steps=20)
    assert losses[-1] < losses[0]


def test_scheduler_from_config():
    model = SimpleModel(hidden_dim=16)
    engine, _, _, sched = deepspeed.initialize(
        model=model,
        config_params=base_config(
            scheduler={"type": "WarmupLR",
                       "params": {"warmup_min_lr": 0,
                                  "warmup_max_lr": 0.01,
                                  "warmup_num_steps": 5}}))
    assert sched is not None
    run_steps(engine, steps=6)
    assert engine.get_lr()[0] == pytest.approx(0.01, rel=1e-3)


def test_zero_stages_loss_parity(eight_devices):
    """ZeRO stages must be numerically equivalent to stage 0 (the reference
    asserts loss parity between configurations; SURVEY §7.2 phase 3)."""
    losses_by_stage = {}
    for stage in [0, 1, 2, 3]:
        model = SimpleModel(hidden_dim=16)
        cfg = base_config(bf16={"enabled": True}) if stage else base_config()
        if stage:
            cfg["zero_optimization"] = {"stage": stage}
        # same init seed → same params
        engine, _, _, _ = deepspeed.initialize(model=model, config_params=cfg)
        losses_by_stage[stage] = run_steps(engine, steps=5)
    for stage in [1, 2, 3]:
        np.testing.assert_allclose(losses_by_stage[stage],
                                   losses_by_stage[0], rtol=2e-2)


def _leaf_shard_fraction(arr):
    """Per-device shard elements / global elements for a jax.Array."""
    shard = arr.addressable_shards[0].data
    return shard.size / arr.size


def test_zero_gradient_and_state_partitioning(eight_devices):
    """ZeRO-2/3 must actually SHARD, not just document sharding: per-device
    gradient shards are 1/N-sized at stage>=2 (reference reduce-scatter
    semantics, stage2.py:675-738), optimizer moments AND the float32 master
    they update 1/N at stage>=1 (the reference's "local fp32 partition",
    stage1.py:246-265). Verified via addressable_shards, not loss values."""
    n = len(eight_devices)
    for stage in [0, 1, 2, 3]:
        model = SimpleModel(hidden_dim=16)
        cfg = base_config(bf16={"enabled": True},
                          zero_optimization={"stage": stage})
        engine, _, _, _ = deepspeed.initialize(model=model, config_params=cfg)
        x, y = random_batch()
        loss = engine(x, y)
        engine.backward(loss)

        grads = engine._grad_acc
        grad_fracs = [_leaf_shard_fraction(g)
                      for g in jax.tree_util.tree_leaves(grads)]
        if stage >= 2:
            assert all(f == pytest.approx(1.0 / n) for f in grad_fracs), \
                "stage {}: grads not 1/{} per device: {}".format(
                    stage, n, grad_fracs)
        elif stage == 0:
            assert all(f == pytest.approx(1.0) for f in grad_fracs)
        # (stage 1 promises nothing of forward()'s gradients: whole at the
        # parent, and since the master is sharded GSPMD may hand the update
        # the shard it needs.)

        engine.step()
        if stage >= 1:
            m_fracs = [_leaf_shard_fraction(g) for g in
                       jax.tree_util.tree_leaves(engine.opt_state["exp_avg"])]
            assert all(f == pytest.approx(1.0 / n) for f in m_fracs)
        p_fracs = [_leaf_shard_fraction(g)
                   for g in jax.tree_util.tree_leaves(engine.params)]
        assert all(f == pytest.approx(1.0 / n if stage else 1.0)
                   for f in p_fracs), (stage, p_fracs)
        assert engine.telemetry.snapshot()["zero_master_shard_share"] == \
            pytest.approx(1.0 / n if stage else 1.0)


def test_zero2_fused_train_batch_grads_sharded(eight_devices):
    """The fused train_batch program reduce-scatters every gradient leaf
    onto ZeRO-2's partition, and says so itself: one ``reduce_scatter``
    over the 'data' axis a parameter leaf in the LOWERED module (what the
    compiler then makes of it is the backend's: the CPU's partitioner
    writes an all-reduce and a slice), where a sharding constraint a leaf
    left the choice to GSPMD."""
    model = SimpleModel(hidden_dim=16)
    engine, _, _, _ = deepspeed.initialize(
        model=model,
        config_params=base_config(bf16={"enabled": True},
                                  zero_optimization={"stage": 2}))
    x, y = random_batch()
    loss = engine.train_batch(batch=(x, y))
    assert np.isfinite(float(loss))
    (fused,) = engine._fused_step_cache.values()
    import jax.numpy as jnp
    lowered = fused.lower(engine.params, engine.opt_state,
                          mesh_lib.shard_batch(engine.mesh, (jnp.asarray(x),
                                                             jnp.asarray(y))),
                          jax.random.PRNGKey(0), jnp.float32(1e-2),
                          jnp.float32(0.9), jnp.float32(0.999)).as_text()
    n_leaves = len(jax.tree_util.tree_leaves(engine.params))
    assert lowered.count("stablehlo.reduce_scatter") == n_leaves
    assert not any("sharding_constraint" in line and '"data"' in line
                   for line in lowered.splitlines())


# ------------------------------------------------- the data-parallel region
# Under data parallelism alone the fused step's forward and backward run per
# chip inside one shard_map over 'data' and ZeRO's collectives are written
# (engine._dp_value_and_grad). The cases below share one engine a (model,
# devices, stage) and one one-device reference a model, built once a module.

VOCAB, WIDTH, SEQ = 1001, 256, 256


def tiny_gpt2(dtype, **kw):
    """The smallest GPT-2 that shows the fault: a tied table whose odd
    vocabulary leaves ZeRO only the FEATURES to split, one layer."""
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    return GPT2LMHeadModel(GPT2Config(
        n_embd=WIDTH, n_layer=1, n_head=4, n_positions=SEQ,
        vocab_size=VOCAB, dropout=0.0, dtype=dtype, **kw))


_TINY_PARAMS = {}


def tiny_gpt2_params(dtype):
    """A fresh copy (engines donate theirs) of one jitted init a dtype."""
    import jax.numpy as jnp
    if dtype not in _TINY_PARAMS:
        model = tiny_gpt2(dtype, use_flash_attention=False)
        _TINY_PARAMS[dtype] = jax.jit(lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])()
    return jax.tree_util.tree_map(jnp.array, _TINY_PARAMS[dtype])


def hlo_collectives(text):
    """(kind, result type) of every collective in optimised HLO ``text``;
    the TPU compiler's fused reduce-scatter counts as one of its own."""
    import re
    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%[\w.-]+ = (.*?) (all-reduce|all-gather|"
                     r"reduce-scatter|all-to-all|collective-permute)"
                     r"(?:-start)?\(", line)
        if m:
            found.append((m.group(2), m.group(1)))
            continue
        m = re.match(r"\s*(?:ROOT )?%[\w.-]+ = (.*?) fusion\(.*"
                     r"calls=%all-reduce-scatter", line)
        if m:
            found.append(("all-reduce-scatter", m.group(1)))
    return found


def partition_leaks(text, vocab=VOCAB, width=WIDTH):
    """What the optimizer's partition did to the MODEL in optimised HLO
    ``text``: every all-to-all (hidden states carried from batch-sharded to
    feature-sharded and back) and every all-reduce / all-gather of an
    array that holds the vocabulary beside a dim that is neither the
    model's width nor a chip's quarter of it (those are the table and its
    gradient): the logits of a head that runs sharded over its contraction."""
    import re
    leaks = []
    for kind, result in hlo_collectives(text):
        if kind == "all-to-all":
            leaks.append((kind, result))
        elif kind in ("all-reduce", "all-gather"):
            for dims in re.findall(r"\[([\d,]+)\]", result):
                dims = [int(d) for d in dims.split(",")]
                if vocab in dims and len(dims) > 1 and width not in dims \
                        and width // 4 not in dims:
                    leaks.append((kind, result))
    return leaks


def optimizer_collectives(text):
    """The lines of optimised HLO ``text`` that hold a collective under the
    step's ``optimizer`` region: none, where its update is local."""
    import re
    return [line for line in text.splitlines()
            if re.search(r" (all-gather|all-reduce|reduce-scatter|"
                         r"collective-permute)(-start)?\(", line)
            and "/optimizer/" in line]


def gradient_scatters(text):
    return [c for c in hlo_collectives(text)
            if c[0] in ("reduce-scatter", "all-reduce-scatter")]


def parameter_gathers(text, params):
    """(dtype, dims, operand, op_name) of every all-gather in optimised
    HLO ``text`` whose result has the shape of a leaf of ``params``."""
    import re
    shapes = {tuple(p.shape) for p in jax.tree_util.tree_leaves(params)}
    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%[\w.-]+ = (\w+)\[([\d,]*)\]\S* "
                     r"all-gather(?:-start)?\(%([\w.-]+)", line)
        if m and tuple(int(d) for d in m.group(2).split(",") if d) in shapes:
            name = re.search(r'op_name="([^"]*)"', line)
            found.append(m.groups() + (name.group(1) if name else "",))
    return found


@pytest.fixture(scope="module")
def zero2_step():
    """(engine, optimised HLO of its fused step, the lowered module):
    ZeRO-2 over 4 devices, bf16 compute, the tiny GPT-2."""
    import jax.numpy as jnp
    eight_devices = jax.devices()
    if len(eight_devices) < 8:
        pytest.skip("needs 8 virtual devices")
    ids = np.random.RandomState(0).randint(0, VOCAB, size=(8, SEQ))
    engine, _, _, _ = deepspeed.initialize(
        model=tiny_gpt2(jnp.bfloat16, use_flash_attention=False),
        model_parameters=tiny_gpt2_params(jnp.bfloat16),
        mesh=mesh_lib.build_mesh(devices=eight_devices[:4]),
        config_params=base_config(bf16={"enabled": True},
                                  zero_optimization={"stage": 2}))
    lowered = engine._build_fused_step().lower(
        engine.params, engine.opt_state,
        mesh_lib.shard_batch(engine.mesh, (jnp.asarray(ids),) * 2),
        jax.random.PRNGKey(0), jnp.float32(1e-2), jnp.float32(0.9),
        jnp.float32(0.999))
    return engine, lowered.compile().as_text(), lowered.as_text()


def test_zero2_step_keeps_the_partition_out_of_the_model(zero2_step):
    """ZeRO-2 on 4 devices, compiled: GSPMD propagated the tied table's
    feature-split gradient into the LM head (``f32[2048,1001]
    all-reduce(%dot)`` a chunk and three ``all-to-all``s on this very
    model, on the CPU's partitioner as on the TPU's:
    test_chip_compile.py holds the same for a described v5e:2x2); under
    the region the model sees no collective at all."""
    _, text, _ = zero2_step
    assert partition_leaks(text) == []
    # The detector is not blind: a logits-shaped all-reduce and an
    # all-to-all as the parent's program holds them.
    assert partition_leaks(
        "  %ar = f32[2048,1001]{1,0} all-reduce(%dot), channel_id=1\n"
        "  %a2a = bf16[4,2,256,64]{3,2,1,0} all-to-all(%x), channel_id=2\n"
        "  %g = bf16[1001,256]{1,0} all-reduce(%dw), channel_id=3\n") == [
        ("all-reduce", "f32[2048,1001]{1,0}"),
        ("all-to-all", "bf16[4,2,256,64]{3,2,1,0}")]


def test_zero2_step_gathers_the_cast_at_its_head(zero2_step):
    """The float32 master is SHARDED (ZeRO's partition is the master's, the
    reference's stage1.py:246-265) and what the step gathers is its bf16
    cast, written by the region under ``zero_gather``, a leaf a gather: in
    the LOWERED module every ``all_gather`` is of bf16 (the CPU's compiler
    widens a bf16 collective to float32 around the wire; the TPU's keeps
    it: test_chip_compile.py), in the compiled one every all-gather of a
    parameter-shaped array gathers a CONVERT's result under
    ``zero_gather``, and no collective belongs to the optimizer, whose
    update is local (the parent gathered the UPDATED float32 master whole
    at the step's tail: 50 ms of GPT-2 XL's dp4 step, nothing to hide it)."""
    import re
    engine, text, lowered = zero2_step
    leaves = jax.tree_util.tree_leaves(engine.params)
    assert all(_leaf_shard_fraction(p) == 0.25 for p in leaves)
    written = re.findall(r'"stablehlo\.all_gather"\(.*', lowered)
    assert len(written) == len(leaves) == engine._zero_leaves[2]
    assert all(re.search(r"-> tensor<[\dx]*xbf16>", line)
               for line in written)
    gathers = parameter_gathers(text, engine.params)
    assert len(gathers) == len(leaves)
    assert all(operand.startswith("convert") and "zero_gather" in name
               for _, _, operand, name in gathers)
    assert optimizer_collectives(text) == []
    # The detector is not blind: the parent's tail gather.
    assert parameter_gathers(
        '  %ag = f32[1001,256]{1,0} all-gather(%add.7), dimensions={1}, '
        'metadata={op_name="jit(train_step)/optimizer/add"}\n',
        engine.params) == [
        ("f32", "1001,256", "add.7", "jit(train_step)/optimizer/add")]
    assert len(optimizer_collectives(
        '  %ag = f32[1001,256]{1,0} all-gather(%add.7), dimensions={1}, '
        'metadata={op_name="jit(train_step)/optimizer/add"}\n')) == 1


def _dp_config(stage):
    # Adam's eps far over the float32 noise of a gradient that is zero but
    # for rounding: at 1e-8 that noise alone moves a parameter by lr.
    cfg = base_config(bf16={"enabled": True},
                      optimizer={"type": "Adam",
                                 "params": {"lr": 1e-2, "eps": 1e-3}})
    if stage:
        cfg["zero_optimization"] = {"stage": stage}
    return cfg


def _dp_batches(kind):
    if kind == "simple":
        return [random_batch(seed=i) for i in range(3)]
    rng = np.random.RandomState(0)
    return [(ids, ids) for ids in rng.randint(0, VOCAB, size=(3, 8, 64))]


def _dp_run(kind, devices, stage, mp=1):
    """(engine, losses, parameters) after 3 fused steps in FLOAT32 (the
    config asks for bf16, which ZeRO's config check wants, and the test
    sets the compute dtype back: no option of the program)."""
    import jax.numpy as jnp
    model, params = SimpleModel(hidden_dim=16), None
    if kind == "gpt2":
        model = tiny_gpt2(jnp.float32, use_flash_attention=False)
        params = tiny_gpt2_params(jnp.float32)
    engine, _, _, _ = deepspeed.initialize(
        model=model, model_parameters=params,
        config_params=_dp_config(stage),
        mesh=mesh_lib.build_mesh(devices=devices, num_mp=mp))
    engine.compute_dtype = jnp.float32
    losses = [float(engine.train_batch(batch=b)) for b in _dp_batches(kind)]
    return engine, losses, jax.tree_util.tree_map(np.asarray, engine.params)


@pytest.fixture(scope="module")
def one_device_runs():
    """kind -> (losses, parameters) of the same steps on ONE device."""
    runs = {}

    def get(kind):
        if kind not in runs:
            runs[kind] = _dp_run(kind, jax.devices()[:1], 0)[1:]
        return runs[kind]
    return get


@pytest.mark.parametrize("kind, n, stage", [
    ("simple", 4, 0), ("simple", 4, 1), ("simple", 4, 2),
    ("simple", 8, 0), ("simple", 8, 1), ("simple", 8, 2),
    ("gpt2", 4, 2)])
def test_dp_region_matches_one_device(kind, n, stage, eight_devices,
                                      one_device_runs):
    """Per-chip mean losses averaged over chips are the global mean, and
    gradients reduce-scattered (stage 2) or summed (stages 0, 1) over
    chips are the global gradient: losses and parameters after 3 steps
    equal one device's, and the gauges say how the leaves left."""
    engine, losses, params = _dp_run(kind, eight_devices[:n], stage)
    ref_losses, ref_params = one_device_runs(kind)
    np.testing.assert_allclose(losses, ref_losses, rtol=0, atol=1e-5)
    for got, want in zip(jax.tree_util.tree_leaves(params),
                         jax.tree_util.tree_leaves(ref_params)):
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)
    n_leaves = len(jax.tree_util.tree_leaves(params))
    gauges = engine.telemetry.snapshot()
    want = (n_leaves, 0) if stage == 2 else (0, n_leaves)
    want += (n_leaves if stage else 0,)
    names = ("zero_scatter_leaves", "zero_psum_leaves", "zero_gather_leaves")
    assert tuple(gauges[k] for k in names) == want
    (row,) = engine.perf_xray()["programs"]
    assert tuple(row[k] for k in names) == want
    share = 1.0 / n if stage else 1.0
    assert gauges["zero_master_shard_share"] == pytest.approx(share)
    assert row["zero_master_shard_share"] == pytest.approx(share)


@pytest.mark.parametrize("n, stage, mp", [(1, 0, 1), (8, 3, 1), (8, 2, 2)])
def test_dp_region_not_entered(n, stage, mp, eight_devices,
                               one_device_runs):
    """One device, stage 3 (the parameters are split too) and a mesh with a
    'model' axis keep the program GSPMD partitions: both gauges read 0,
    and the losses are one device's all the same."""
    engine, losses, _ = _dp_run("simple", eight_devices[:n], stage, mp=mp)
    np.testing.assert_allclose(losses, one_device_runs("simple")[0],
                               rtol=0, atol=1e-5)
    gauges = engine.telemetry.snapshot()
    assert (gauges["zero_scatter_leaves"], gauges["zero_psum_leaves"],
            gauges["zero_gather_leaves"]) == (0, 0, 0)


def test_dp_region_drops_out_by_chip_and_by_seed(eight_devices):
    """The dropout key is folded with the chip's index: chips that hold
    identical rows draw different masks (a gradient that is a mean over 4
    masks takes values no single mask has), and the same seed gives the
    same losses again."""
    import flax.linen as nn
    import jax.numpy as jnp

    class Dropped(nn.Module):
        @nn.compact
        def __call__(self, x, y):
            w = self.param("w", nn.initializers.ones, (x.shape[-1],))
            keep = nn.Dropout(0.5, deterministic=False)(jnp.ones_like(x))
            return jnp.mean(jnp.sum(keep * w * x, axis=-1) * y)

    def build():
        engine, _, _, _ = deepspeed.initialize(
            model=Dropped(), config_params=_dp_config(2),
            mesh=mesh_lib.build_mesh(devices=eight_devices[:4]))
        return engine

    x, y = np.ones((4, 32), np.float32), np.ones((4,), np.float32)
    engine = build()
    losses = [float(engine.train_batch(batch=(x, y))) for _ in range(3)]
    again = build()
    assert losses == [float(again.train_batch(batch=(x, y)))
                      for _ in range(3)]
    assert len(set(losses)) == 3

    def loss_fn(p, args, rng):
        return Dropped().apply({"params": p}, *args, rngs={"dropout": rng})

    specs = engine._dp_region_specs((x, y))
    _, grads = jax.jit(lambda p, r: engine._dp_value_and_grad(
        loss_fn, specs, p, (jnp.asarray(x), jnp.asarray(y)), r))(
        {"w": jnp.ones((32,))}, jax.random.PRNGKey(0))
    # One row a chip, kept entries 2: a mask shared by all four chips
    # would leave only 0 and 2.
    assert set(np.unique(np.asarray(grads["w"]))) - {0.0, 2.0}


@pytest.mark.parametrize("stage", [1, 2])
def test_three_call_path_matches_fused_on_a_sharded_master(
        stage, eight_devices, one_device_runs):
    """``forward`` / ``backward`` / ``step`` on the sharded master: the
    parameters after two steps are the fused path's (float32), and the
    compiled forward-and-backward gathers the WEIGHTS once and nothing
    else: every all-gather is parameter-shaped, no all-to-all, no
    logits-shaped all-reduce (``_cast_to_compute`` ends in a constraint to
    the layout without 'data', so the master's split stays out of the
    model under GSPMD too)."""
    import jax.numpy as jnp
    fused, _, want = _dp_run("simple", eight_devices[:4], stage)
    engine, _, _, _ = deepspeed.initialize(
        model=SimpleModel(hidden_dim=16), config_params=_dp_config(stage),
        mesh=mesh_lib.build_mesh(devices=eight_devices[:4]))
    engine.compute_dtype = jnp.float32
    for x, y in _dp_batches("simple"):
        engine.backward(engine(x, y))
        engine.step()
    leaves = jax.tree_util.tree_leaves(engine.params)
    assert all(_leaf_shard_fraction(p) == 0.25 for p in leaves)
    for got, ref in zip(leaves, jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(got), ref, rtol=0, atol=1e-5)

    ids = np.random.RandomState(0).randint(0, VOCAB, size=(8, 64))
    gpt2, _, _, _ = deepspeed.initialize(
        model=tiny_gpt2(jnp.bfloat16, use_flash_attention=False),
        model_parameters=tiny_gpt2_params(jnp.bfloat16),
        mesh=mesh_lib.build_mesh(devices=eight_devices[:4]),
        config_params=base_config(bf16={"enabled": True},
                                  zero_optimization={"stage": stage}))
    inputs = mesh_lib.shard_batch(gpt2.mesh, (jnp.asarray(ids),) * 2)
    text = gpt2._get_fwd_bwd(2, {}, (), True).lower(
        gpt2.params, inputs, {}, jax.random.PRNGKey(0),
        jnp.float32(1.0)).compile().as_text()
    assert partition_leaks(text) == []
    gathers = parameter_gathers(text, gpt2.params)
    assert len(gathers) == len(jax.tree_util.tree_leaves(gpt2.params)) == \
        len([c for c in hlo_collectives(text) if c[0] == "all-gather"])
    # ... of the CAST (the CPU's compiler widens it again for the wire).
    assert all(operand.startswith("convert") for _, _, operand, _ in gathers)


def test_zero2_checkpoint_of_a_sharded_master_loads_at_another_dp(
        tmp_path, eight_devices, one_device_runs):
    """A ZeRO-2 checkpoint written from the sharded master at dp 4 holds
    the WHOLE master (host copies, the parent's file) and loads at dp 2:
    parameters and moments bit for bit, the parameters one device's, and
    the next step on both meshes agrees."""
    import jax.numpy as jnp
    engine, _, params = _dp_run("simple", eight_devices[:4], 2)
    engine.save_checkpoint(str(tmp_path), tag="dp4")
    for got, want in zip(jax.tree_util.tree_leaves(params),
                         jax.tree_util.tree_leaves(
                             one_device_runs("simple")[1])):
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)

    other, _, _, _ = deepspeed.initialize(
        model=SimpleModel(hidden_dim=16), config_params=_dp_config(2),
        mesh=mesh_lib.build_mesh(devices=eight_devices[:2]))
    other.compute_dtype = jnp.float32
    x, y = random_batch()
    other(x, y)  # materialize shapes before loading over them
    path, _ = other.load_checkpoint(str(tmp_path))
    assert path is not None and other.global_steps == 3
    for tree, saved in ((other.params, engine.params),
                        (other.opt_state, engine.opt_state)):
        for a, b in zip(jax.tree_util.tree_leaves(other._to_host(tree)),
                        jax.tree_util.tree_leaves(engine._to_host(saved))):
            np.testing.assert_array_equal(a, b)
    assert all(_leaf_shard_fraction(p) == 0.5
               for p in jax.tree_util.tree_leaves(other.params))
    batch = random_batch(seed=7)
    assert float(other.train_batch(batch=batch)) == pytest.approx(
        float(engine.train_batch(batch=batch)), abs=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(other.params),
                    jax.tree_util.tree_leaves(engine.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=1e-5)


@pytest.mark.parametrize("zero, share", [
    ({"stage": 0}, 1.0), ({"stage": 3}, 1.0 / 8),
    ({"stage": 2, "cpu_offload": True}, 1.0)])
def test_master_layout_unchanged_where_zero_does_not_split_it(
        zero, share, eight_devices):
    """Stage 0 keeps the master whole, stage 3 computes on its shards as
    before, and under ZeRO-Offload the master is on the HOST: what
    ``param_sharding`` places is the compute copy, whole on every chip."""
    engine, _, _, _ = deepspeed.initialize(
        model=SimpleModel(hidden_dim=16),
        config_params=base_config(bf16={"enabled": True},
                                  zero_optimization=zero))
    x, y = random_batch()
    engine(x, y)
    assert all(_leaf_shard_fraction(p) == pytest.approx(share)
               for p in jax.tree_util.tree_leaves(engine.params))
    assert engine._compute_sharding is None
    assert engine.telemetry.snapshot()["zero_master_shard_share"] == \
        pytest.approx(share)


def test_a_new_steps_trace_gets_a_megabyte_frame_and_no_collector():
    """The dispatch that traces a new fused step runs inside one frame of
    2^17 + 64 slots (CPython opens a 2 MB chunk for it, so no call of the
    trace crosses a chunk's end) with the cyclic collector paused, and
    gives the collector back whatever happens."""
    import gc

    from deepspeed_tpu.runtime import engine as engine_mod

    seen = []
    assert engine_mod._traced_with_room(
        lambda: seen.append(gc.isenabled()) or 7) == 7
    assert seen == [False] and gc.isenabled()
    (roomy,) = engine_mod._ROOMY
    assert roomy.__code__.co_nlocals == 2 ** 17 + 65
    with pytest.raises(ZeroDivisionError):
        engine_mod._traced_with_room(lambda: 1 / 0)
    assert gc.isenabled()


_EAGER_INITS = {}


def _lazy_cases():
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    ids = np.random.RandomState(0).randint(0, 97, size=(8, 16))
    return {
        "simple": (lambda: SimpleModel(hidden_dim=16), random_batch()),
        "gpt2": (lambda: GPT2LMHeadModel(GPT2Config(
            n_embd=32, n_layer=1, n_head=2, n_positions=16, vocab_size=97,
            dropout=0.0, dtype=jnp.float32, use_flash_attention=False)),
            (ids, ids)),
    }


@pytest.mark.parametrize("entry", ["forward", "train_batch"])
@pytest.mark.parametrize("offload", [False, True], ids=["device", "offload"])
@pytest.mark.parametrize("name", ["simple", "gpt2"])
def test_an_engine_given_no_parameters_initialises_them_as_the_eager_init(
        name, offload, entry, eight_devices):
    """``_lazy_init`` (ONE compiled ``module.init``, for ``forward`` and
    ``train_batch`` alike) against the eager init each of them carried: the
    same two keys in the same order, the same tree, the shardings the ZeRO
    stage gives it, values within float32 rounding, and the optimizer's state
    where the eager paths put it. The host tier (``cpu_offload``) keeps its
    own: ``forward`` leaves ``opt_state`` alone there, and ``train_batch``
    never reached its own copy there (it hands such a step to ``forward`` /
    ``backward`` / ``step`` before it looks at the parameters), so it leaves
    it alone too."""
    make, batch = _lazy_cases()[name]
    zero = {"stage": 2, "cpu_offload": True} if offload else {"stage": 2}
    engine, _, _, _ = deepspeed.initialize(
        model=make(), config_params=base_config(
            zero_optimization=zero, bf16={"enabled": True}))
    assert engine.params is None and engine.opt_state is None
    assert engine._offload_mode() == offload

    rng, key_params = jax.random.split(engine._rng)
    rng, key_dropout = jax.random.split(rng)
    inputs = mesh_lib.shard_batch(
        engine.mesh, tuple(jax.numpy.asarray(x) for x in batch))
    # the eager init, operation by operation: once a model and pair of keys
    drawn = (name, np.asarray(jax.random.key_data(engine._rng)).tobytes())
    if drawn not in _EAGER_INITS:
        _EAGER_INITS[drawn] = make().init(
            {"params": key_params, "dropout": key_dropout}, *inputs)["params"]
    want = _EAGER_INITS[drawn]
    # (the master lies as its moments from stage 1 on; the host tier's
    # device copy stays whole)
    shardings, _, _ = mesh_lib.zero_shardings(engine.mesh, want, 2,
                                              master_on_chips=not offload)

    if entry == "forward":
        engine(*batch)
    else:
        engine.train_batch(batch=batch)
    if entry == "forward" or offload:
        # the next key drawn after the two of the init is the step's
        assert np.array_equal(jax.random.key_data(engine._rng),
                              jax.random.key_data(
                                  jax.random.split(rng)[0]))

    got = engine.params
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    flat = jax.tree_util.tree_leaves_with_path
    for (path, a), (_, b), (_, sh) in zip(flat(got), flat(want),
                                          flat(shardings)):
        assert a.shape == b.shape, path
        assert a.sharding.is_equivalent_to(sh, a.ndim), (path, a.sharding)
        if entry == "forward":
            # no step has moved them yet (nor has the host tier's handed
            # the device its copy in the compute dtype)
            assert a.dtype == b.dtype, path
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7, err_msg=str(path))
    if offload and entry == "forward":
        assert engine.opt_state is None
    elif offload:
        # the host tier's own, made by its first step: nothing on the device
        assert not any(isinstance(leaf, jax.Array) for leaf in
                       jax.tree_util.tree_leaves(engine.opt_state))
    else:
        assert set(engine.opt_state) >= {"step", "exp_avg", "exp_avg_sq"}
        assert jax.tree_util.tree_structure(engine.opt_state["exp_avg"]) \
            == jax.tree_util.tree_structure(want)
        assert int(engine.opt_state["step"]) == \
            (0 if entry == "forward" else 1)


def test_train_batch_fused_path():
    model = SimpleModel(hidden_dim=16)
    engine, _, _, _ = deepspeed.initialize(
        model=model, config_params=base_config(bf16={"enabled": True}))
    losses = []
    for i in range(20):
        x, y = random_batch(seed=i % 3)
        loss = engine.train_batch(batch=(x, y))
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    assert engine.global_steps == 20


def test_checkpoint_save_load_roundtrip(tmp_path):
    model = SimpleModel(hidden_dim=16)
    cfg = base_config()
    engine, _, _, _ = deepspeed.initialize(model=model, config_params=cfg)
    run_steps(engine, steps=5)
    params_before = engine._to_host(engine.params)
    engine.save_checkpoint(str(tmp_path), tag="tag1")
    assert (tmp_path / "latest").read_text() == "tag1"
    assert (tmp_path / "tag1" / "mp_rank_00_model_states.pt").exists()

    model2 = SimpleModel(hidden_dim=16)
    engine2, _, _, _ = deepspeed.initialize(model=model2, config_params=cfg)
    # materialize params with one fwd so shapes exist, then load over them
    x, y = random_batch()
    engine2(x, y)
    path, _ = engine2.load_checkpoint(str(tmp_path))
    assert path is not None
    assert engine2.global_steps == engine.global_steps
    params_after = engine2._to_host(engine2.params)
    import jax
    for a, b in zip(jax.tree_util.tree_leaves(params_before),
                    jax.tree_util.tree_leaves(params_after)):
        np.testing.assert_allclose(a, b, rtol=1e-6)

    # training continues from the checkpoint
    losses = run_steps(engine2, steps=3)
    assert np.isfinite(losses).all()


def test_checkpoint_restores_scheduler_and_loss_scaler(tmp_path):
    """Reference test_checkpointing.py also round-trips LR-scheduler and
    fp16 loss-scaler state: resumed training must continue the schedule and
    the dynamic scale, not restart them."""
    def make():
        cfg = base_config(
            fp16={"enabled": True, "initial_scale_power": 8,
                  "hysteresis": 1},
            scheduler={"type": "WarmupLR",
                       "params": {"warmup_min_lr": 0.0,
                                  "warmup_max_lr": 1e-2,
                                  "warmup_num_steps": 10}})
        return deepspeed.initialize(model=SimpleModel(hidden_dim=16),
                                    config_params=cfg)[0]

    engine = make()
    run_steps(engine, steps=4)
    # mutate dynamic-scaler state so restoration is observable
    engine.loss_scaler.cur_scale /= 4
    engine.loss_scaler.cur_iter = 17
    lr_before = engine.get_lr()
    engine.save_checkpoint(str(tmp_path), tag="sched")

    engine2 = make()
    x, y = random_batch()
    engine2(x, y)
    engine2.load_checkpoint(str(tmp_path))
    assert engine2.global_steps == 4
    assert engine2.loss_scaler.cur_scale == engine.loss_scaler.cur_scale
    assert engine2.loss_scaler.cur_iter == 17
    assert engine2.get_lr() == lr_before
    assert engine2.lr_scheduler.state_dict() == \
        engine.lr_scheduler.state_dict()
    losses = run_steps(engine2, steps=2)
    assert np.isfinite(losses).all()


def test_checkpoint_zero_files(tmp_path):
    model = SimpleModel(hidden_dim=16)
    cfg = base_config(bf16={"enabled": True},
                      zero_optimization={"stage": 1})
    engine, _, _, _ = deepspeed.initialize(model=model, config_params=cfg)
    run_steps(engine, steps=2)
    engine.save_checkpoint(str(tmp_path), tag="z")
    assert (tmp_path / "z" / "zero_pp_rank_0_mp_rank_00optim_states.pt").exists()


def test_elastic_zero_checkpoint_repartition(tmp_path, eight_devices):
    """Elastic ZeRO checkpointing (reference stage1.py:848-1078,
    engine.py:1376-1442): optimizer state saved at dp=8 is written as 8
    world-size-agnostic shard files and reloads BITWISE onto a dp=4 mesh."""
    model = SimpleModel(hidden_dim=16)
    cfg = base_config(bf16={"enabled": True},
                      zero_optimization={"stage": 2})
    engine, _, _, _ = deepspeed.initialize(model=model, config_params=cfg)
    run_steps(engine, steps=3)
    engine.save_checkpoint(str(tmp_path), tag="el")
    for r in range(8):
        assert (tmp_path / "el" /
                "zero_pp_rank_{}_mp_rank_00optim_states.pt".format(r)).exists()
    saved_state = engine._to_host(engine.opt_state)
    saved_params = engine._to_host(engine.params)

    mesh4 = mesh_lib.build_mesh(devices=jax.devices()[:4])
    engine2, _, _, _ = deepspeed.initialize(
        model=SimpleModel(hidden_dim=16), mesh=mesh4,
        config_params=base_config(bf16={"enabled": True},
                                  zero_optimization={"stage": 2}))
    x, y = random_batch()
    engine2(x, y)  # materialize shapes before loading over them
    path, _ = engine2.load_checkpoint(str(tmp_path))
    assert path is not None
    for a, b in zip(jax.tree_util.tree_leaves(saved_state),
                    jax.tree_util.tree_leaves(
                        engine2._to_host(engine2.opt_state))):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(jax.tree_util.tree_leaves(saved_params),
                    jax.tree_util.tree_leaves(
                        engine2._to_host(engine2.params))):
        np.testing.assert_array_equal(a, b)
    # moments/params re-partitioned onto the dp=4 mesh, and training resumes
    leaf = jax.tree_util.tree_leaves(engine2.opt_state["exp_avg"])[0]
    assert len(leaf.sharding.device_set) == 4
    losses = run_steps(engine2, steps=2)
    assert np.isfinite(losses).all()


def test_pg_correctness_toggle(eight_devices):
    """reference stage2.py:23-25 pg_correctness_test analogue: with the
    debug toggle on, every training step cross-checks the sharded-path
    gradients against a replicated unconstrained program."""
    from deepspeed_tpu.runtime import engine as engine_mod

    model = SimpleModel(hidden_dim=16)
    cfg = base_config(bf16={"enabled": True},
                      zero_optimization={"stage": 2})
    engine, _, _, _ = deepspeed.initialize(model=model, config_params=cfg)
    engine_mod.pg_correctness_test = True
    try:
        losses = run_steps(engine, steps=3)
    finally:
        engine_mod.pg_correctness_test = False
    assert np.isfinite(losses).all()


def test_multi_output_model():
    """Multi-loss models (reference tests/unit/test_multi_output_model.py):
    the TPU engine's convention is out[0] = the scalar to differentiate, so
    a weighted multi-loss model returns (total, loss_a, loss_b) — training
    minimizes the weighted total while the per-task losses ride along as
    aux outputs."""
    import flax.linen as nn
    import jax.numpy as jnp

    class MultiOutputModel(nn.Module):
        hidden_dim: int = 8

        @nn.compact
        def __call__(self, xa, ya, xb, yb):
            dense = nn.Dense(self.hidden_dim, use_bias=False)

            def ce(x, y):
                logp = nn.log_softmax(dense(x))
                return -jnp.mean(
                    jnp.take_along_axis(logp, y[..., None], axis=-1))

            loss_a, loss_b = ce(xa, ya), ce(xb, yb)
            return 1.0 * loss_a + 0.5 * loss_b, loss_a, loss_b

    engine, _, _, _ = deepspeed.initialize(
        model=MultiOutputModel(),
        config_params=base_config(gradient_accumulation_steps=2,
                                  train_batch_size=16))
    rng = np.random.RandomState(0)
    xa = rng.randn(4, 8).astype(np.float32)
    xb = rng.randn(4, 8).astype(np.float32)
    ya = rng.randint(0, 8, size=(4,))
    yb = rng.randint(0, 8, size=(4,))
    totals = []
    for _ in range(8):  # 2 micro-steps per optimizer step (gas=2)
        total, la, lb = engine(xa, ya, xb, yb)
        np.testing.assert_allclose(float(total),
                                   1.0 * float(la) + 0.5 * float(lb),
                                   rtol=1e-5)
        engine.backward(total)
        engine.step()
        totals.append(float(total))
    assert engine.global_steps == 4  # gas=2: half as many optimizer steps
    assert totals[-1] < totals[0]


def test_dataloader_integration():
    class DS:
        def __len__(self):
            return 64

        def __getitem__(self, i):
            rng = np.random.RandomState(i)
            return rng.randn(16).astype(np.float32), rng.randint(0, 16)

    model = SimpleModel(hidden_dim=16)
    engine, _, loader, _ = deepspeed.initialize(
        model=model, config_params=base_config(), training_data=DS())
    assert loader is not None
    n = 0
    for x, y in loader:
        loss = engine(x, y)
        engine.backward(loss)
        engine.step()
        n += 1
    assert n == len(loader)
