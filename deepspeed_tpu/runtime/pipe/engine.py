"""PipelineEngine — executes PipeSchedule instructions over the 'pipe' mesh axis.

TPU-native re-design of reference runtime/pipe/engine.py:45-1172. The
reference is a per-rank interpreter with blocking NCCL p2p
(broadcast-in-2-rank-groups, p2p.py:31-55). In single-controller JAX, ONE
process drives every stage's devices, so the engine:

- materializes each stage's layer parameters on that stage's devices
  (a ('data','model') submesh of the global mesh's pipe slice);
- compiles one forward (jax.vjp over a jitted stage function) per stage —
  forward and backward are each a single XLA executable per stage;
- interprets the SAME TrainSchedule/InferenceSchedule instruction streams as
  the reference, for all stages interleaved. Send/Recv become device-to-device
  transfers (ICI) through a mailbox; a dependency-driven scheduler loop
  preserves the schedule's pairwise send/recv ordering without deadlock.
- relies on JAX async dispatch for overlap: stage s+1's forward is enqueued
  while stage s computes its next micro-batch, so the 1F1B wavefront really
  overlaps across chips despite the Python-level interpreter.

Tied layers share one parameter pytree (single-controller aliasing), so
ReduceTiedGrads reduces to summing the accumulated grads of each use —
matching reference module.py:405-474 semantics with no collective.
"""

import os
import pickle

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.parallel import mesh as mesh_lib
from deepspeed_tpu.runtime.engine import DeepSpeedEngine
from deepspeed_tpu.runtime.pipe import p2p
from deepspeed_tpu.runtime.pipe import schedule as p_schedule
from deepspeed_tpu.runtime.pipe.module import (
    LayerSpec,
    PipelineModule,
    TiedLayerSpec,
)
from deepspeed_tpu.runtime.utils import ensure_directory_exists
from deepspeed_tpu.utils.logging import log_dist, logger

def _missing_dropout_rng(err):
    """Is ``err`` flax's complaint about an unprovided 'dropout' PRNG
    stream? Eval forwards pass no dropout rng BY DESIGN (deterministic
    eval), so a layer that calls ``make_rng('dropout')`` unconditionally
    fails here with a message that doesn't say which convention it broke —
    _exec_forward_pass re-raises it with the pointer."""
    try:
        from flax.errors import InvalidRngError
    except ImportError:  # flax layout change: fall back to the message
        InvalidRngError = ()
    msg = str(err)
    if isinstance(err, InvalidRngError):
        return "dropout" in msg
    return "dropout" in msg and "rng" in msg.lower()


def _is_flax_module(layer):
    return hasattr(layer, "init") and hasattr(layer, "apply")


class PipelineEngine(DeepSpeedEngine):
    """Training engine for PipelineModule models (reference pipe/engine.py:45)."""

    def __init__(self, *args, **kwargs):
        model = kwargs.get("model", args[1] if len(args) > 1 else None)
        assert isinstance(model, PipelineModule), \
            "model must be a PipelineModule"
        # Build a pipe-axis mesh before the config's batch-triangle math runs,
        # and work out the PP x DP grid: each pipeline stage owns a
        # ('data','model') submesh and shards its micro-batch over 'data', so
        # the config's world size (= data-parallel size) is devices-per-stage
        # (reference PipelineParallelGrid semantics, pipe/topology.py:246-455).
        if kwargs.get("mesh") is None:
            from deepspeed_tpu.parallel.mesh import build_mesh
            n_dev = jax.device_count()
            pp = model.num_stages if n_dev % model.num_stages == 0 \
                and n_dev >= model.num_stages else 1
            # devices deliberately NOT passed: build_mesh then applies the
            # topology-aware (ICI/DCN) arrangement on real TPU.
            kwargs["mesh"] = build_mesh(num_dp=n_dev // pp, num_mp=1,
                                        num_pp=pp)
        _mesh = kwargs["mesh"]
        _n = _mesh.devices.size
        _mp = _mesh.shape.get(mesh_lib.MODEL_AXIS, 1)
        if _n % model.num_stages == 0 and _n >= model.num_stages:
            self._pipe_dp = (_n // model.num_stages) // _mp
        else:
            # Fewer devices than stages (round-robin placement): no
            # data-parallel replication within stages.
            self._pipe_dp = 1
        super().__init__(*args, **kwargs)
        assert not self.elasticity_enabled(), \
            "Elasticity is not currently supported with pipeline parallelism."

        self.pipe_module = self.module
        self.num_stages = self.pipe_module.num_stages
        self.micro_batches = self.gradient_accumulation_steps()

        # Per-stage device assignment: slice the global mesh's 'pipe' axis;
        # if the mesh has no pipe axis (or wrong size), split devices evenly.
        self.stage_devices = self._assign_stage_devices()
        self.stage_meshes = self._build_stage_meshes()

        # Materialized state (lazy, from first batch shapes):
        self.layers = [self.pipe_module.build_layer(i)
                       for i in range(self.pipe_module.num_layers())]
        self.layer_params = [None] * len(self.layers)  # pytree or None
        self.tied_param_owner = {}  # tied key -> first layer idx
        self.pipe_opt_state = None
        self._stage_fwd = {}  # stage_id -> jitted stage function
        self._stage_fwd_bwd = {}  # stage_id -> (fwd+res jit, bwd jit)
        self._stage_bwd_local = {}  # stage_id -> local-grad bwd (1-bit frozen)
        self._stage_opt_jit = {}  # (stage, idxs, compressed) -> jitted update
        self._grad_acc_jit = {}  # stage_id -> jitted grad accumulate
        self._seed_cache = {}  # (shape, dtype, scale) -> backward seed
        self._handlers = {}  # instruction type -> bound handler
        # Shardings are constructed once per stage, not per instruction —
        # NamedSharding construction showed up on the dispatch profile.
        self._stage_rep_sh = [NamedSharding(m, P())
                              for m in self.stage_meshes]
        self._stage_batch_sh = [NamedSharding(m, P(mesh_lib.DATA_AXIS))
                                for m in self.stage_meshes]
        self._materialized = False

        self.grad_acc = [None] * len(self.layers)  # per-layer grad pytrees
        self.agg_loss = None

    def _config_world_size(self):
        # Data-parallel size WITHIN each stage: micro-batches are sharded over
        # the stage submesh's 'data' axis, so batch math multiplies by it.
        return getattr(self, "_pipe_dp", 1)

    # ------------------------------------------------------------- placement

    def _assign_stage_devices(self):
        devices = list(self.mesh.devices.reshape(-1))
        n = len(devices)
        if n >= self.num_stages and n % self.num_stages == 0:
            per = n // self.num_stages
            return [devices[s * per:(s + 1) * per]
                    for s in range(self.num_stages)]
        # Fewer devices than stages: round-robin.
        return [[devices[s % n]] for s in range(self.num_stages)]

    def _build_stage_meshes(self):
        """One ('data','model') Mesh per stage over that stage's devices —
        the single-controller analogue of the reference's per-stage dp/slice
        process groups (pipe/topology.py:252-455)."""
        mp = self.mp_world_size
        meshes = []
        for devs in self.stage_devices:
            if len(devs) % mp == 0 and len(devs) >= mp:
                dp, mp_local = len(devs) // mp, mp
            else:
                # Stage device count not a multiple of the model axis (e.g.
                # round-robin placement with fewer devices than stages):
                # fall back to pure-dp within the stage rather than crash or
                # drop chips.
                dp, mp_local = len(devs), 1
            arr = np.asarray(devs).reshape(dp, mp_local)
            meshes.append(Mesh(arr, (mesh_lib.DATA_AXIS, mesh_lib.MODEL_AXIS)))
        return meshes

    def _stage_of_layer(self, idx):
        return self.pipe_module.stage_owner(idx)

    def _place(self, tree, stage_id):
        """Place a pytree (params, opt state) on a stage's submesh:
        replicated, except leaves matching the tensor-parallel rules when
        the stage mesh has a 'model' axis — PP x TP composition (the
        reference's slice-group partitioning, pipe/engine.py:504-534)."""
        mesh = self.stage_meshes[stage_id]
        if mesh.shape.get(mesh_lib.MODEL_AXIS, 1) > 1 and tree is not None:
            sh, _, _ = mesh_lib.zero_shardings(
                mesh, tree, 0,
                tp_rules=getattr(self.pipe_module, "tp_rules", None))
            return jax.device_put(tree, sh)
        return jax.device_put(tree, self._stage_rep_sh[stage_id])

    def _place_batch(self, tree, stage_id):
        """Shard batch-leading arrays over the stage's 'data' axis; leaves
        whose leading dim does not divide stay replicated."""
        mesh = self.stage_meshes[stage_id]
        dp = mesh.shape.get(mesh_lib.DATA_AXIS, 1)
        batch_sh = self._stage_batch_sh[stage_id]
        rep = self._stage_rep_sh[stage_id]

        def _put(x):
            if dp > 1 and hasattr(x, "shape") and len(x.shape) > 0 \
                    and x.shape[0] % dp == 0:
                return jax.device_put(x, batch_sh)
            return jax.device_put(x, rep)

        return jax.tree_util.tree_map(_put, tree)

    # --------------------------------------------------------- materialization

    def _materialize(self, first_batch):
        """Init every layer's params by tracing a micro-batch through the
        stages (shape inference), placing each stage's params on its devices."""
        x = first_batch[0]
        x = jnp.asarray(x)
        rng = self._next_rng()
        for idx, layer in enumerate(self.layers):
            x = self._place_batch(x, self._stage_of_layer(idx))
            spec = self.pipe_module.layer_specs[idx]
            tied_key = spec.key if isinstance(spec, TiedLayerSpec) else None
            if tied_key is not None and tied_key in self.tied_param_owner:
                # Per-stage replica of the tied weights (the reference
                # replicates tied layers across their stages and allreduces
                # their grads, module.py:405-474).
                owner = self.tied_param_owner[tied_key]
                self.layer_params[idx] = self._place(
                    self.layer_params[owner], self._stage_of_layer(idx))
            elif _is_flax_module(layer):
                if self.pipe_module.seed_layers:
                    seed = self.pipe_module.base_seed + idx
                    if self.pipe_module.seed_fn is not None:
                        # Reference module.py calls seed_fn(seed) as the
                        # per-layer seeding action; a returned PRNGKey is used
                        # directly, other returns keep the default key.
                        maybe_key = self.pipe_module.seed_fn(seed)
                        rng = maybe_key if maybe_key is not None and \
                            hasattr(maybe_key, "dtype") else \
                            jax.random.PRNGKey(seed)
                    else:
                        rng = jax.random.PRNGKey(seed)
                rng, sub = jax.random.split(rng)
                variables = layer.init({"params": sub, "dropout": sub}, x)
                params = variables.get("params", {})
                self.layer_params[idx] = self._place(
                    params, self._stage_of_layer(idx))
                if tied_key is not None:
                    self.tied_param_owner[tied_key] = idx
            else:
                self.layer_params[idx] = None  # parameterless callable
            x = self._apply_layer(idx, self.layer_params[idx], x,
                                  jax.random.PRNGKey(0))
        # Optimizer state per parameterized layer, co-located with its stage.
        if self.optimizer is not None:
            if self._onebit_pp_capable():
                # 1-bit Adam over PP x DP: error feedback is per-rank state
                # (reference keeps it in each rank's optimizer,
                # onebit_adam.py:295-309) — one row per worker of the
                # stage's data axis, sliced inside the compressed
                # shard_map update.
                from deepspeed_tpu.runtime.fp16.onebit_adam import (
                    init_onebit_adam_state)
                init = lambda p: init_onebit_adam_state(
                    p, self._pipe_dp, per_worker_rows=True)
            else:
                init = self.optimizer.init_state
            self.pipe_opt_state = [
                self._place(init(p),
                            self._stage_of_layer(i)) if p is not None else None
                for i, p in enumerate(self.layer_params)
            ]
        self._materialized = True

    def _apply_layer(self, idx, params, x, rng):
        layer = self.layers[idx]
        spec = self.pipe_module.layer_specs[idx]
        fwd = getattr(spec, "forward_fn", None)
        if fwd is not None:
            # TiedLayerSpec.forward_fn: alternate forward for a tied reuse
            # (reference module.py:225-231). TPU signature:
            # forward_fn(module, params, x).
            return fwd(layer, params, x)
        if _is_flax_module(layer):
            return layer.apply({"params": params}, x, rngs={"dropout": rng})
        return layer(x)

    def _onebit_spmd_eligible(self):
        # The pipeline engine has its own per-layer optimizer path; the
        # base engine's 1-bit shard_map hot path (and its per-worker
        # error-row state layout) never applies here.
        return False

    def _onebit_pp_capable(self):
        """Whether THIS pipeline can run 1-bit Adam's compressed momentum
        exchange over each stage's data-axis submesh (BASELINE config #5:
        PP x DP + 1-bit; reference custom_collectives.py:10-155 composes
        with any engine because it is optimizer-level). Requires real
        data-parallel replication within stages and no tensor axis (the
        local-grad shard_map treats the whole stage submesh as 'data')."""
        from deepspeed_tpu.runtime.fp16.onebit_adam import OnebitAdam
        return (isinstance(self.optimizer, OnebitAdam)
                and self._pipe_dp > 1 and self.mp_world_size <= 1)

    def _onebit_pp_compressed_active(self):
        """True once the optimizer crossed freeze_step: backward switches
        to per-worker local grads and OptimizerStep to the compressed
        exchange (one re-trace at the boundary, like the base engine)."""
        return self._onebit_pp_capable() and self.optimizer.adam_freeze_key

    def _get_stage_bwd_local(self, stage_id):
        """Backward variant for the 1-bit compression phase: param grads
        come back UN-averaged, one row per data-parallel worker, stacked
        on a leading axis sharded over the stage's 'data' axis. The dense
        bwd's implicit GSPMD all_reduce of param cotangents (replicated
        params, sharded batch) is thereby removed from the wire — the
        frozen phase's only exchange is the sign-packed momentum in
        OptimizerStep (reference disables dense allreduce past
        freeze_step, onebit_adam.py:369-372)."""
        if stage_id in self._stage_bwd_local:
            return self._stage_bwd_local[stage_id]
        from jax import shard_map

        mesh = self.stage_meshes[stage_id]
        axis = mesh_lib.DATA_AXIS
        raw_fn = self._build_stage_fn(stage_id)
        tm = jax.tree_util.tree_map

        def worker(params_list, x, labels, rng, seed):
            def f(ps, xx):
                return raw_fn(ps, xx, labels, rng)

            _, vjp = jax.vjp(f, params_list, x)
            param_grads, in_grad = vjp(seed)
            # [1, ...] local row -> stacks to [dp, ...] under out_spec.
            return tm(lambda g: g[None], param_grads), in_grad

        def bwd(params_list, x, labels, rng, seed):
            # Prefix specs: P() replicates every leaf, P(axis) shards every
            # leaf's dim 0 (the batch dim of x/labels/mid-stage seeds, the
            # added worker row of param grads); a scalar loss seed (last
            # stage) is replicated.
            seed_spec = P(axis) if getattr(seed, "ndim", 0) > 0 else P()
            return shard_map(
                worker, mesh=mesh,
                in_specs=(P(), P(axis), P(axis), P(), seed_spec),
                out_specs=(P(axis), P(axis)),
                check_vma=False)(params_list, x, labels, rng, seed)

        jitted = jax.jit(bwd)
        self._stage_bwd_local[stage_id] = jitted
        return jitted

    def _get_stage_fn(self, stage_id, with_dropout=True):
        """One jitted function running all of a stage's layers; last stage
        appends the loss_fn. Returns (out_or_loss, ...). ``with_dropout``
        False (eval) omits the dropout rng — layers keying train/eval on
        rng presence (has_rng) then run deterministically."""
        key = (stage_id, with_dropout)
        if key in self._stage_fwd:
            return self._stage_fwd[key]
        jitted = jax.jit(self._build_stage_fn(stage_id, with_dropout))
        self._stage_fwd[key] = jitted
        return jitted

    def _build_stage_fn(self, stage_id, with_dropout=True):
        """The raw (unjitted) stage function — shared by the eval path
        (_get_stage_fn jits it directly) and the training path
        (_get_stage_fwd_bwd differentiates it under jit)."""
        start, stop = self.pipe_module.stage_layer_range(stage_id)
        layers = self.layers
        layer_params_idx = list(range(start, stop))
        loss_fn = self.pipe_module.loss_fn
        is_last = stage_id == self.num_stages - 1
        apply_layer_fns = []
        ckpt_interval = self.pipe_module.activation_checkpoint_interval
        for i in layer_params_idx:
            layer = layers[i]
            fwd = getattr(self.pipe_module.layer_specs[i], "forward_fn", None)
            if fwd is not None:
                apply_layer_fns.append(
                    lambda p, x, rng, _l=layer, _f=fwd: _f(_l, p, x))
            elif _is_flax_module(layer):
                apply_layer_fns.append(
                    lambda p, x, rng, _l=layer:
                    _l.apply({"params": p}, x,
                             rngs={"dropout": rng} if with_dropout
                             else {}))
            else:
                apply_layer_fns.append(lambda p, x, rng, _l=layer: _l(x))

        def run_span(span, params_span, h, rngs):
            for fn, p, r in zip(span, params_span, rngs):
                h = fn(p, h, r)
            return h

        def stage_fn(params_list, x, labels, rng):
            h = x
            n = len(apply_layer_fns)
            rngs = list(jax.random.split(rng, max(n, 1)))
            if ckpt_interval > 0:
                # Remat contiguous spans of ckpt_interval layers: only span
                # boundaries keep activations (reference checkpointing
                # semantics, module.py forward with checkpoint_interval).
                for start in range(0, n, ckpt_interval):
                    stop = min(start + ckpt_interval, n)
                    h = jax.checkpoint(run_span, static_argnums=(0,))(
                        tuple(apply_layer_fns[start:stop]),
                        params_list[start:stop], h, rngs[start:stop])
            else:
                h = run_span(tuple(apply_layer_fns), params_list, h, rngs)
            if is_last and loss_fn is not None:
                return loss_fn(h, labels)
            return h

        return stage_fn

    def _get_stage_fwd_bwd(self, stage_id):
        """Pre-compiled (forward, backward) pair for the training path.

        Calling ``jax.vjp`` eagerly per micro-batch re-traces the stage on
        every ForwardPass (~3 ms of host time per instruction when it was
        profiled, round 2, CPU) and the returned closure then
        executes the transposed jaxpr op-by-op on every BackwardPass —
        host-bound dispatch that caps pipeline MFU. Instead both
        directions are compiled ONCE per stage: the forward is the plain
        stage jit, and the backward is a single program that recomputes
        the stage forward and transposes it (``jax.vjp`` *inside* jit).

        The recompute is deliberate, not a workaround: (a) the 1F1B
        window keeps up to `stages` micro-batches in flight per stage, so
        storing only the stage INPUT (instead of every vjp residual)
        shrinks in-flight activation memory to one tensor per micro-batch
        — the reason the reference defaults pipelines to activation
        checkpointing too; (b) residual-passing via jax.closure_convert
        cannot hoist integer-typed residuals (gather indices, dropout
        bits), so it breaks on real losses/stages. Every instruction
        after warmup is a cached-executable dispatch, letting the Python
        interpreter run ahead of the devices (the overlap the schedule
        needs; the reference hot loop pipe/engine.py:1146-1171 likewise
        dispatches prebuilt kernels per instruction)."""
        if stage_id in self._stage_fwd_bwd:
            return self._stage_fwd_bwd[stage_id]
        raw_fn = self._build_stage_fn(stage_id)
        fwd = self._get_stage_fn(stage_id)

        @jax.jit
        def bwd(params_list, x, labels, rng, seed):
            def f(ps, xx):
                return raw_fn(ps, xx, labels, rng)

            _, vjp = jax.vjp(f, params_list, x)
            return vjp(seed)

        pair = (fwd, bwd)
        self._stage_fwd_bwd[stage_id] = pair
        return pair

    # ----------------------------------------------------------- train_batch

    def train_batch(self, data_iter=None, batch=None):
        """Run one full 1F1B batch: gas micro-batches through all stages, then
        the optimizer step (reference pipe/engine.py:244-318)."""
        assert data_iter is not None or batch is not None
        if batch is not None:
            # A directly-passed batch is the GLOBAL batch: split it into gas
            # micro-batches along axis 0 (replicating it would train on
            # duplicated data while accounting for train_batch_size samples).
            gas = self.micro_batches
            leading = np.asarray(batch[0]).shape[0] if isinstance(
                batch, (tuple, list)) else np.asarray(batch).shape[0]
            if gas > 1:
                assert leading % gas == 0, \
                    "train_batch(batch=...) with gradient_accumulation_steps" \
                    "={} needs a leading batch dim divisible by it, got {}" \
                    .format(gas, leading)
                mb = leading // gas
                if isinstance(batch, (tuple, list)):
                    micro = [tuple(np.asarray(t)[i * mb:(i + 1) * mb]
                                   for t in batch) for i in range(gas)]
                else:
                    micro = [np.asarray(batch)[i * mb:(i + 1) * mb]
                             for i in range(gas)]
                data_iter = iter(micro)
            else:
                data_iter = iter([batch])

        self._exec_schedule_cls(p_schedule.TrainSchedule, data_iter,
                                train=True)
        self.global_steps += 1
        self.global_samples += self.train_batch_size()
        if hasattr(self.optimizer, "notify_step"):
            # Freeze-boundary bookkeeping (reference onebit_adam.py:369-372)
            # — past freeze_step the backward switches to local grads and
            # OptimizerStep to the compressed momentum exchange.
            self.optimizer.notify_step(self.global_steps -
                                       self.skipped_steps)
        self._last_loss = self.agg_loss
        self._tensorboard_step_events()
        if self.lr_scheduler is not None:
            self.lr_scheduler.step()
        if self.global_steps % self.steps_per_print() == 0:
            self._report_progress(self.global_steps)
        return self.agg_loss

    def eval_batch(self, data_iter):
        """Pipelined evaluation via InferenceSchedule (reference :320-387)."""
        self._exec_schedule_cls(p_schedule.InferenceSchedule, data_iter,
                                train=False)
        return self.agg_loss

    def forward(self, *args, **kwargs):
        raise RuntimeError(
            "Only train_batch() is accessible in pipeline mode.")

    def backward(self, *args, **kwargs):
        raise RuntimeError(
            "Only train_batch() is accessible in pipeline mode.")

    def step(self, *args, **kwargs):
        raise RuntimeError(
            "Only train_batch() is accessible in pipeline mode.")

    # ------------------------------------------------------ schedule executor

    def _exec_schedule_cls(self, sched_cls, data_iter, train):
        if not self._materialized:
            peek = next(data_iter)
            self._materialize(peek)
            # rebuild iterator including the peeked batch
            import itertools
            data_iter = itertools.chain([peek], data_iter)

        S = self.num_stages
        scheds = [sched_cls(micro_batches=self.micro_batches, stages=S,
                            stage_id=s) for s in range(S)]
        step_lists = [list(s.steps()) for s in scheds]
        total_steps = len(step_lists[0])
        assert all(len(sl) == total_steps for sl in step_lists)

        # Execution state
        state = {
            "buffers": [
                {"inputs": {}, "outputs": {}, "labels": {}, "vjp": {},
                 "in_grad": {}, "out_grad": {}}
                for _ in range(S)
            ],
            # the p2p transport: FIFO (src_stage, dst_stage) payload queues
            "mail": p2p.Mailbox(),
            "data_iter": data_iter,
            "losses": [],
            "train": train,
            # first/last stages draw from the same micro-batch stream;
            # cache per micro-batch so both see identical data.
            "mb_cache": {},
            "mb_next": [0, 0],  # per first/last endpoint load counters
        }

        for step_id in range(total_steps):
            # Dependency-driven execution of this step across stages: run each
            # stage's cmd queue; a Recv blocks until its mailbox has data.
            queues = [list(step_lists[s][step_id]) for s in range(S)]
            progress = True
            while any(queues) and progress:
                progress = False
                for s in range(S):
                    while queues[s]:
                        cmd = queues[s][0]
                        if isinstance(cmd, (p_schedule.RecvActivation,
                                            p_schedule.RecvGrad)):
                            src = s + 1 if isinstance(
                                cmd, p_schedule.RecvGrad) else s - 1
                            if not state["mail"].has(src, s):
                                break  # blocked; try other stages first
                        self._dispatch(cmd, s, state)
                        queues[s].pop(0)
                        progress = True
            if any(queues):
                raise RuntimeError(
                    "pipeline schedule deadlock at step {}: {}".format(
                        step_id, queues))

        if state["losses"]:
            if all(getattr(l, "ndim", 0) == 0 for l in state["losses"]):
                self.agg_loss = float(
                    np.mean([self._fetch_scalar(l)
                             for l in state["losses"]]))
            else:
                # loss_fn-less eval: expose raw last-stage outputs instead.
                self.outputs = state["losses"]
                self.agg_loss = None
        return self.agg_loss

    def _fetch_scalar(self, x):
        """Host value of a (possibly remote-stage) device scalar. Under
        multi-controller, the loss lives on the LAST stage's devices —
        another process cannot float() it. The stage's lowest-ranked
        controller reads its local (replicated) shard and host-broadcasts
        it; every process runs this symmetrically, like every other
        instruction."""
        if not hasattr(x, "sharding") or jax.process_count() == 1:
            return float(x)
        src = sorted(x.sharding.device_set,
                     key=lambda d: (d.process_index, d.id))
        # Every predicate below must evaluate IDENTICALLY on all
        # processes (it is derived from the sharding, not from which
        # process runs it) — a per-process branch would desync the
        # symmetric transfer protocol.
        owners = {d.process_index for d in src}
        if owners == set(range(jax.process_count())) and \
                x.sharding.is_fully_replicated:
            # Every process already holds a replica: pure local reads.
            return float(np.asarray(x.addressable_shards[0].data))
        # Cross-host device_put (the same transport the schedule's
        # Send/Recv instructions ride — ICI/DCN on real pods) onto a
        # SAME-SIZED device list spread round-robin over every process,
        # so each controller ends up with a local replica to read. All
        # processes execute this symmetrically, like every instruction.
        key = tuple(d.id for d in src)
        sh = self._fetch_shardings = getattr(self, "_fetch_shardings", {})
        if key not in sh:
            by_proc = {}
            for d in self.mesh.devices.reshape(-1):
                by_proc.setdefault(d.process_index, []).append(d)
            picked, i = [], 0
            while len(picked) < len(src):
                for p in sorted(by_proc):
                    if len(picked) < len(src) and i < len(by_proc[p]):
                        picked.append(by_proc[p][i])
                i += 1
            sh[key] = NamedSharding(
                Mesh(np.asarray(picked), ("replica",)), P())
        rep = jax.device_put(x, sh[key])
        shards = rep.addressable_shards
        assert shards, ("pipeline stage smaller than the process count: "
                        "no local replica to read the loss from")
        return float(np.asarray(shards[0].data))

    def _dispatch(self, cmd, stage_id, state):
        handler = self._handlers.get(type(cmd))
        if handler is None:
            handler = getattr(
                self, "_exec_" + _camel_to_snake(type(cmd).__name__))
            self._handlers[type(cmd)] = handler
        handler(cmd, stage_id, state)

    # ------------------------------------------------------------ instruction
    # handlers (reference pipe/engine.py:494-1171, _INSTRUCTION_MAP)

    def _load_micro_batch(self, state, mb_idx):
        if mb_idx not in state["mb_cache"]:
            state["mb_cache"][mb_idx] = next(state["data_iter"])
        batch = state["mb_cache"][mb_idx]
        # Evict entries both endpoints (first stage: inputs, last stage:
        # labels) have consumed — bounds the cache to the pipeline depth
        # instead of the whole global batch.
        watermark = min(state["mb_next"])
        for k in [k for k in state["mb_cache"] if k < watermark]:
            del state["mb_cache"][k]
        return batch

    def _exec_load_micro_batch(self, cmd, stage_id, state):
        buf = state["buffers"][stage_id]
        endpoint = 0 if stage_id == 0 else 1
        mb_idx = state["mb_next"][endpoint]
        state["mb_next"][endpoint] += 1
        batch = self._load_micro_batch(state, mb_idx)
        if stage_id == 0:
            buf["inputs"][cmd.buffer_id] = self._place_batch(
                jnp.asarray(batch[0]), stage_id)
        if stage_id == self.num_stages - 1:
            buf["labels"][cmd.buffer_id] = self._place_batch(
                jnp.asarray(batch[1]), stage_id)

    def _exec_forward_pass(self, cmd, stage_id, state):
        buf = state["buffers"][stage_id]
        x = buf["inputs"][cmd.buffer_id]
        labels = buf["labels"].get(cmd.buffer_id)
        start, stop = self.pipe_module.stage_layer_range(stage_id)
        params_list = [self.layer_params[i] for i in range(start, stop)]
        rng = self._next_rng()

        if state["train"]:
            fwd, _ = self._get_stage_fwd_bwd(stage_id)
            out = fwd(params_list, x, labels, rng)
            # Backward residual = the stage INPUTS (recompute-style): one
            # tensor per in-flight micro-batch instead of every vjp
            # intermediate — see _get_stage_fwd_bwd.
            buf["vjp"][cmd.buffer_id] = (params_list, x, labels, rng)
        else:
            # eval: no dropout rng — layers keying on has_rng("dropout")
            # run deterministically (the reference eval_batch flips
            # module.eval() the same way).
            try:
                out = self._get_stage_fn(stage_id, with_dropout=False)(
                    params_list, x, labels, rng)
            except Exception as e:
                if not _missing_dropout_rng(e):
                    raise
                raise RuntimeError(
                    "pipeline eval forward on stage {} failed because a "
                    "layer requested the 'dropout' PRNG, which eval_batch "
                    "does not provide. Gate the make_rng('dropout') call "
                    "on self.has_rng('dropout') and run deterministically "
                    "when it is absent — the train/eval contract in "
                    "docs/tutorials/pipeline.md ('The dropout rng "
                    "contract for pipeline layers').".format(stage_id)
                ) from e
        buf["outputs"][cmd.buffer_id] = out
        if stage_id == self.num_stages - 1:
            # Reference semantics (pipe/engine.py:537-543): with a loss_fn the
            # last stage computes loss_fn(out, labels); without one the
            # module's own output IS the loss.
            if self.pipe_module.loss_fn is None and state["train"] and \
                    getattr(out, "ndim", 0) != 0:
                raise RuntimeError(
                    "last pipeline stage produced a non-scalar output and no "
                    "loss_fn was given; provide loss_fn to PipelineModule or "
                    "make the last layer return a scalar loss")
            state["losses"].append(out)

    def _exec_backward_pass(self, cmd, stage_id, state):
        buf = state["buffers"][stage_id]
        residuals = buf["vjp"].pop(cmd.buffer_id)
        if stage_id == self.num_stages - 1:
            out = buf["outputs"][cmd.buffer_id]
            # Constant seed (ones / gas, x loss scale): built once per
            # (shape, scale) and reused — two eager dispatches per
            # micro-batch showed up on the dispatch profile.
            scale = (self.loss_scaler.loss_scale
                     if self.loss_scaler is not None else 1.0)
            key = (getattr(out, "shape", ()), str(getattr(out, "dtype", "")),
                   float(scale))
            seed = self._seed_cache.get(key)
            if seed is None:
                seed = jnp.ones_like(out) * (scale / self.micro_batches)
                self._seed_cache[key] = seed
        else:
            seed = buf["out_grad"].pop(cmd.buffer_id)
        if self._onebit_pp_compressed_active():
            # 1-bit compression phase: per-worker local grads, no dense
            # allreduce on the wire (see _get_stage_bwd_local).
            bwd = self._get_stage_bwd_local(stage_id)
        else:
            _, bwd = self._get_stage_fwd_bwd(stage_id)
        b_params, b_x, b_labels, b_rng = residuals
        param_grads, in_grad = bwd(b_params, b_x, b_labels, b_rng, seed)
        buf["in_grad"][cmd.buffer_id] = in_grad
        start, stop = self.pipe_module.stage_layer_range(stage_id)
        live = [(j, gi) for j, gi in enumerate(range(start, stop))
                if param_grads[j] is not None]
        if all(self.grad_acc[gi] is None for _, gi in live):
            for j, gi in live:
                self.grad_acc[gi] = param_grads[j]
        else:
            # One jitted add over the whole stage's grads instead of an
            # eager per-leaf tree_map per layer (dispatch-profile item).
            acc_fn = self._grad_acc_jit.get(stage_id)
            if acc_fn is None:
                acc_fn = jax.jit(lambda a, b: jax.tree_util.tree_map(
                    lambda x_, y_: x_ + y_, a, b), donate_argnums=0)
                self._grad_acc_jit[stage_id] = acc_fn
            acc = acc_fn(tuple(self.grad_acc[gi] for _, gi in live),
                         tuple(param_grads[j] for j, _ in live))
            for n, (_, gi) in enumerate(live):
                self.grad_acc[gi] = acc[n]
        buf["outputs"].pop(cmd.buffer_id, None)

    def _exec_send_activation(self, cmd, stage_id, state):
        out = state["buffers"][stage_id]["outputs"][cmd.buffer_id]
        dst = stage_id + 1
        state["mail"].post(stage_id, dst, self._place_batch(out, dst))

    def _exec_recv_activation(self, cmd, stage_id, state):
        src = stage_id - 1
        payload = state["mail"].take(src, stage_id)
        state["buffers"][stage_id]["inputs"][cmd.buffer_id] = payload

    def _exec_send_grad(self, cmd, stage_id, state):
        in_grad = state["buffers"][stage_id]["in_grad"].pop(cmd.buffer_id)
        dst = stage_id - 1
        state["mail"].post(stage_id, dst, self._place_batch(in_grad, dst))

    def _exec_recv_grad(self, cmd, stage_id, state):
        src = stage_id + 1
        payload = state["mail"].take(src, stage_id)
        state["buffers"][stage_id]["out_grad"][cmd.buffer_id] = payload

    def _exec_reduce_tied_grads(self, cmd, stage_id, state):
        if stage_id != 0:
            return  # single-controller: fold once globally, not per stage
        # Fold every tied slot's accumulated grads into the owner slot.
        for key, idxs in self.pipe_module.tied_specs.items():
            owner = self.tied_param_owner.get(key)
            if owner is None:
                continue
            owner_stage = self._stage_of_layer(owner)
            total = None
            for i in idxs:
                if self.grad_acc[i] is not None:
                    g = self._place(self.grad_acc[i], owner_stage)
                    total = g if total is None else \
                        jax.tree_util.tree_map(lambda a, b: a + b, total, g)
            for i in idxs:
                self.grad_acc[i] = total if i == owner else None

    def _exec_reduce_grads(self, cmd, stage_id, state):
        # DP gradient reduction is a GSPMD constraint inside the stage jit on
        # TPU; nothing to do here (reference does bucketed allreduce,
        # pipe/engine.py:221-242).
        pass

    def _get_stage_opt_jit(self, stage_id, idxs, compressed):
        """One jitted optimizer update covering ALL of a stage's layers —
        a single cached-executable dispatch per stage per step instead of
        one per layer (dispatch-profile item; the reference's analogue is
        one multi-tensor-apply launch over chunked params,
        csrc/adam/multi_tensor_adam.cu).

        With ``compressed`` (1-bit Adam past freeze_step), the update runs
        under shard_map over the stage's data axis: each worker feeds its
        LOCAL gradient row into local momentum and the only exchange is
        the sign-packed compressed_allreduce — uint8 n/8 + scales on the
        wire (reference custom_collectives.py:10-155)."""
        key = (stage_id, idxs, compressed)
        fn = self._stage_opt_jit.get(key)
        if fn is not None:
            return fn
        opt = self.optimizer
        tm = jax.tree_util.tree_map

        if not compressed:
            # Client (duck-typed) optimizers satisfy the historical
            # contract update(p, g, s, lr=, betas=); only pass the newer
            # eps/weight_decay kwargs to optimizers that accept them.
            import inspect
            try:
                accepts = set(inspect.signature(opt.update).parameters)
            except (TypeError, ValueError):
                accepts = set()
            extra = {"eps", "weight_decay"} <= accepts

            def multi(ps, gs, ss, lr, b1, b2, eps, wd):
                kw = dict(eps=eps, weight_decay=wd) if extra else {}
                outs = [opt.update(p, g, s, lr=lr, betas=(b1, b2), **kw)
                        for p, g, s in zip(ps, gs, ss)]
                return (tuple(o[0] for o in outs),
                        tuple(o[1] for o in outs))

            fn = jax.jit(multi, donate_argnums=(0, 2))
        else:
            from jax import shard_map

            from deepspeed_tpu.runtime.fp16.onebit_adam import (
                onebit_adam_update)

            mesh = self.stage_meshes[stage_id]
            axis = mesh_lib.DATA_AXIS
            dp = mesh.shape.get(axis, 1)
            freeze_step = opt.freeze_step

            def worker(ps, gs, ss, lr, b1, b2, eps, wd):
                new_ps, new_ss = [], []
                for p, g, s in zip(ps, gs, ss):
                    st = dict(s)
                    st["worker_error"] = tm(lambda e: e[0],
                                            s["worker_error"])
                    st["server_error"] = tm(lambda e: e[0],
                                            s["server_error"])
                    np_, ns = onebit_adam_update(
                        p, tm(lambda a: a[0], g), st, lr=lr, beta1=b1,
                        beta2=b2, eps=eps, weight_decay=wd,
                        freeze_step=freeze_step, axis_name=axis,
                        world_size=dp, frozen=True)
                    ns["worker_error"] = tm(lambda e: e[None],
                                            ns["worker_error"])
                    ns["server_error"] = tm(lambda e: e[None],
                                            ns["server_error"])
                    new_ps.append(np_)
                    new_ss.append(ns)
                return tuple(new_ps), tuple(new_ss)

            def state_spec(s):
                return {
                    "step": P(),
                    "exp_avg": tm(lambda _: P(), s["exp_avg"]),
                    "exp_avg_sq": tm(lambda _: P(), s["exp_avg_sq"]),
                    "worker_error": tm(lambda _: P(axis),
                                       s["worker_error"]),
                    "server_error": tm(lambda _: P(axis),
                                       s["server_error"]),
                }

            def multi(ps, gs, ss, lr, b1, b2, eps, wd):
                sspec = tuple(state_spec(s) for s in ss)
                return shard_map(
                    worker, mesh=mesh,
                    in_specs=(P(), P(axis), sspec, P(), P(), P(), P(),
                              P()),
                    out_specs=(P(), sspec),
                    check_vma=False)(ps, gs, ss, lr, b1, b2, eps, wd)

            fn = jax.jit(multi, donate_argnums=(0, 2))
        self._stage_opt_jit[key] = fn
        return fn

    def _exec_optimizer_step(self, cmd, stage_id, state):
        if stage_id != 0:
            return  # single-controller: run the global update once
        group = self.optimizer.param_groups[0]
        lr = jnp.float32(group["lr"])
        beta1, beta2 = group.get("betas", (0.9, 0.999))
        clip = self.gradient_clipping()
        compressed = self._onebit_pp_compressed_active()

        # fp16 dynamic-loss-scale bookkeeping (reference pipe engine inherits
        # the full fp16 step path): grads carry the scale from the backward
        # seed; on overflow the step is skipped and the scale shrinks.
        if self.loss_scaler is not None:
            from deepspeed_tpu.runtime.utils import jit_has_overflow
            cur_scale = self.loss_scaler.loss_scale
            # Dispatch every layer's check first, sync once — one blocking
            # device_get per layer would serialize L host round-trips.
            flags = [jit_has_overflow(g)
                     for g in self.grad_acc if g is not None]
            overflow = any(bool(f) for f in jax.device_get(flags))
            self.loss_scaler.update_scale(overflow)
            if overflow:
                self.skipped_steps += 1
                log_dist("PIPELINE OVERFLOW! Skipping step. Attempted loss "
                         "scale: {}, reducing to {}".format(
                             cur_scale, self.loss_scaler.loss_scale),
                         ranks=[0])
                self.grad_acc = [None] * len(self.layers)
                return
            inv = 1.0 / cur_scale
            if inv != 1.0:
                self.grad_acc = [
                    jax.tree_util.tree_map(
                        lambda x: (x.astype(jnp.float32) * inv).astype(
                            x.dtype), g) if g is not None else None
                    for g in self.grad_acc]

        # Global grad clip across all layers (reference clips globally).
        # Layers live on different stage submeshes, so per-layer squared norms
        # are reduced on each stage's devices and combined on host; the scale
        # factor is then broadcast back into each stage's program.
        if clip > 0.0 and compressed:
            self._warn_onebit_clip_once(clip)
            clip = 0.0
        if clip > 0.0:
            from deepspeed_tpu.runtime.utils import jit_global_norm_sq
            sqs = [jit_global_norm_sq(g)
                   for g in self.grad_acc if g is not None]
            total_norm = sum(float(s) for s in jax.device_get(sqs)) ** 0.5
            coef = min(clip / (total_norm + 1e-6), 1.0)
            if coef < 1.0:
                self.grad_acc = [
                    jax.tree_util.tree_map(
                        lambda x: (x.astype(jnp.float32) * coef).astype(
                            x.dtype), g) if g is not None else None
                    for g in self.grad_acc]

        # One batched update per STAGE (not per layer): eps/weight_decay
        # ride along as traced args so later param_group mutations (not
        # just lr/betas) take effect without a re-trace.
        scalars = (lr, jnp.float32(beta1), jnp.float32(beta2),
                   jnp.float32(group.get("eps", 1e-8)),
                   jnp.float32(group.get("weight_decay", 0.0)))
        seen_tied = set()
        for sid in range(self.num_stages):
            start, stop = self.pipe_module.stage_layer_range(sid)
            idxs = []
            for i in range(start, stop):
                if self.layer_params[i] is None or self.grad_acc[i] is None:
                    continue
                spec = self.pipe_module.layer_specs[i]
                if isinstance(spec, TiedLayerSpec):
                    if spec.key in seen_tied:
                        continue
                    seen_tied.add(spec.key)
                idxs.append(i)
            if not idxs:
                continue
            fn = self._get_stage_opt_jit(sid, tuple(idxs), compressed)
            new_ps, new_ss = fn(
                tuple(self.layer_params[i] for i in idxs),
                tuple(self.grad_acc[i] for i in idxs),
                tuple(self.pipe_opt_state[i] for i in idxs), *scalars)
            for n, i in enumerate(idxs):
                self.layer_params[i] = new_ps[n]
                self.pipe_opt_state[i] = new_ss[n]
                spec = self.pipe_module.layer_specs[i]
                # refresh the per-stage replicas of tied weights
                if isinstance(spec, TiedLayerSpec):
                    for j in self.pipe_module.tied_specs[spec.key]:
                        if j != i:
                            self.layer_params[j] = self._place(
                                new_ps[n], self._stage_of_layer(j))
        self.grad_acc = [None] * len(self.layers)

    # ------------------------------------------------------------- checkpoint

    def save_checkpoint(self, save_dir, tag=None, client_state=None,
                        save_latest=True):
        """Per-layer checkpoint files (reference pipe/engine.py:1110-1126,
        module.py:536-546) so a different pipeline split can reload."""
        if tag is None:
            tag = "global_step{}".format(self.global_steps)
        ckpt_dir = os.path.join(save_dir, str(tag))
        for idx, params in enumerate(self.layer_params):
            if params is None:
                continue
            path = self.pipe_module.ckpt_layer_path(ckpt_dir, idx)
            ensure_directory_exists(path)
            with open(path, "wb") as f:
                pickle.dump(self._to_host(params), f)
        # Optimizer state per (dp, mp) rank, like the reference's
        # zero_pp_rank_*optim_states.pt files (engine.py:1557-1561).
        if self.pipe_opt_state is not None:
            opt_path = os.path.join(
                ckpt_dir, "zero_pp_rank_0_mp_rank_00optim_states.pt")
            ensure_directory_exists(opt_path)
            with open(opt_path, "wb") as f:
                pickle.dump([self._to_host(s) if s is not None else None
                             for s in self.pipe_opt_state], f)
        self._save_ckpt_meta(ckpt_dir, save_dir, tag, client_state,
                             save_latest)
        return True

    def _save_ckpt_meta(self, ckpt_dir, save_dir, tag, client_state,
                        save_latest):
        """Shared meta/'latest' writer for both pipeline engines — one
        place so the checkpoint header never drifts between them."""
        meta = {
            "global_steps": self.global_steps,
            "global_samples": self.global_samples,
            "skipped_steps": self.skipped_steps,
            "num_layers": len(self.layers),
            "parts": self.pipe_module.parts,
            "lr_scheduler": self.lr_scheduler.state_dict()
            if self.lr_scheduler else None,
        }
        if client_state:
            meta.update(client_state)
        with open(os.path.join(ckpt_dir, "mp_rank_00_model_states.pt"),
                  "wb") as f:
            pickle.dump(meta, f)
        if save_latest:
            with open(os.path.join(save_dir, "latest"), "w") as fd:
                fd.write(str(tag))

    def _load_ckpt_meta(self, ckpt_dir):
        """Counterpart reader; returns the saved client_state."""
        meta_path = os.path.join(ckpt_dir, "mp_rank_00_model_states.pt")
        if not os.path.exists(meta_path):
            return {}
        with open(meta_path, "rb") as f:
            meta = pickle.load(f)
        self.global_steps = meta.get("global_steps", 0)
        self.global_samples = meta.get("global_samples", 0)
        self.skipped_steps = meta.get("skipped_steps", 0)
        if self.lr_scheduler and meta.get("lr_scheduler"):
            self.lr_scheduler.load_state_dict(meta["lr_scheduler"])
        return {k: v for k, v in meta.items()
                if k not in ("global_steps", "global_samples",
                             "skipped_steps", "num_layers", "parts",
                             "lr_scheduler")}

    def load_checkpoint(self, load_dir, tag=None, **kwargs):
        if tag is None:
            latest = os.path.join(load_dir, "latest")
            if not os.path.isfile(latest):
                return None, None
            with open(latest) as fd:
                tag = fd.read().strip()
        ckpt_dir = os.path.join(load_dir, str(tag))
        assert self._materialized, \
            "run one train_batch (or materialize) before loading a pipeline " \
            "checkpoint so layer shapes exist"
        for idx in range(len(self.layers)):
            path = self.pipe_module.ckpt_layer_path(ckpt_dir, idx)
            if os.path.exists(path):
                with open(path, "rb") as f:
                    params = pickle.load(f)
                self.layer_params[idx] = self._place(
                    jax.tree_util.tree_map(jnp.asarray, params),
                    self._stage_of_layer(idx))
        opt_path = os.path.join(ckpt_dir,
                                "zero_pp_rank_0_mp_rank_00optim_states.pt")
        if kwargs.get("load_optimizer_states", True) and \
                os.path.exists(opt_path) and self.pipe_opt_state is not None:
            with open(opt_path, "rb") as f:
                saved = pickle.load(f)
            self.pipe_opt_state = [
                self._place(jax.tree_util.tree_map(jnp.asarray, s),
                            self._stage_of_layer(i)) if s is not None else None
                for i, s in enumerate(saved)]
        return ckpt_dir, self._load_ckpt_meta(ckpt_dir)


def _camel_to_snake(name):
    out = []
    for i, ch in enumerate(name):
        if ch.isupper() and i > 0:
            out.append("_")
        out.append(ch.lower())
    return "".join(out)
