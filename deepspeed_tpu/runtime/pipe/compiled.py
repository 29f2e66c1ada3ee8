"""CompiledPipelineEngine — the ENTIRE pipeline schedule as ONE XLA program.

The instruction-interpreter PipelineEngine (engine.py) preserves the
reference's per-instruction execution model (reference pipe/engine.py:45-1172
interprets TrainSchedule commands per rank); its Python dispatch loop is
fine single-controller but (a) costs host time per instruction and (b)
cannot drive cross-process stage submeshes in lockstep. This engine is the
TPU-native alternative: the whole GPipe-style schedule — micro-batch
wavefront, inter-stage transfers, backward, optimizer — is traced into a
single jitted SPMD program over a (pipe, data) mesh:

- per-stage block parameters are STACKED on a leading [S] axis sharded
  over 'pipe', so each stage's weights live only on its pipe slice;
- one `lax.scan` over M + S - 1 clock ticks advances the micro-batch
  wavefront; the slab of per-stage activations is sharded
  P('pipe', 'data'), and the per-tick `jnp.roll` across the pipe axis is
  compiled by GSPMD into a collective_permute riding ICI — the
  inter-stage Send/Recv of the reference schedule with zero host
  involvement;
- every stage's compute at a tick is a `vmap` over the stacked axis, so
  XLA schedules all S stage computations of a tick concurrently on their
  slices (the 1F1B wavefront overlap, enforced by the compiler instead of
  asynchronous dispatch);
- the backward is `jax.grad` THROUGH the scan (each tick rematerialized
  via `jax.checkpoint`), and the optimizer update runs in the same
  program.

Because it is one global-mesh program, it runs unchanged under
multi-controller `jax.distributed` — the execution shape of a real
multi-host pod — where the interpreter cannot.

Constraints (v1): the pipelined run must be STRUCTURALLY UNIFORM — a
maximal run of identical LayerSpecs divisible by the stage count, with the
same activation shape in and out. Layers before/after the run (embedding,
head) execute data-parallel outside the pipelined scan, like the
first/last-stage extras of a conventional pipeline. TiedLayerSpec is not
supported here (use the interpreter engine).

Select with ``PipelineModule(..., compiled=True)``.
"""

import os
import pickle

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.parallel import mesh as mesh_lib
from deepspeed_tpu.runtime.pipe.engine import PipelineEngine, _is_flax_module
from deepspeed_tpu.runtime.pipe.module import LayerSpec, TiedLayerSpec
from deepspeed_tpu.runtime.utils import ensure_directory_exists
from deepspeed_tpu.utils.logging import log_dist


# Disjoint fold domains for the prologue / epilogue per-micro-batch
# dropout streams: the pipelined stages fold (tick t, stage s) directly
# off ``rng``, so the micro-batch folds must branch off a distinct
# subtree or micro-batch m would collide with tick t == m.
_PRO_FOLD = 0x5f0a0b01
_EPI_FOLD = 0x5f0a0b02


def _spec_key(spec):
    return (spec.typename, tuple(spec.module_args),
            tuple(sorted(spec.module_kwargs.items())))


def _uniform_run(specs, num_stages):
    """(i0, i1) of the longest run of identical plain LayerSpecs whose
    length is a positive multiple of ``num_stages``."""
    best = None
    i = 0
    n = len(specs)
    while i < n:
        if not isinstance(specs[i], LayerSpec) or \
                isinstance(specs[i], TiedLayerSpec):
            i += 1
            continue
        j = i + 1
        while j < n and isinstance(specs[j], LayerSpec) and \
                not isinstance(specs[j], TiedLayerSpec) and \
                _spec_key(specs[j]) == _spec_key(specs[i]):
            j += 1
        length = ((j - i) // num_stages) * num_stages
        if length >= num_stages and (best is None or
                                     length > best[1] - best[0]):
            best = (i, i + length)
        i = j
    return best


class CompiledPipelineEngine(PipelineEngine):
    """One-program pipeline engine (see module docstring)."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        specs = self.pipe_module.layer_specs
        if any(isinstance(s, TiedLayerSpec) for s in specs):
            raise ValueError(
                "compiled pipeline does not support TiedLayerSpec; use "
                "the interpreter PipelineEngine (compiled=False)")
        pp = self.mesh.shape.get(mesh_lib.PIPE_AXIS, 1)
        if pp != self.num_stages:
            raise ValueError(
                "compiled pipeline needs a mesh whose 'pipe' axis equals "
                "num_stages (got pipe={}, num_stages={}): with fewer "
                "devices than stages the shard_map worker would silently "
                "drop stages. Provide enough devices (device_count "
                "divisible by num_stages) or a matching mesh.".format(
                    pp, self.num_stages))
        run = _uniform_run(specs, self.num_stages)
        if run is None:
            raise ValueError(
                "compiled pipeline needs a run of >= num_stages identical "
                "LayerSpecs (a uniform block stack); got {}".format(
                    [repr(s) for s in specs]))
        self._run = run
        self._blocks_per_stage = (run[1] - run[0]) // self.num_stages
        self._block_module = specs[run[0]].build()
        self._pro_layers = [self.layers[i] for i in range(run[0])]
        self._epi_layers = [self.layers[i] for i in range(run[1], len(specs))]
        self._cp_params = None  # {"prologue": [...], "blocks": st, "epilogue": [...]}
        self._cp_opt_state = None
        self._step_fn = None
        if self.loss_scaler is not None:
            raise ValueError(
                "compiled pipeline v1 does not implement fp16 dynamic "
                "loss scaling (overflow-skip needs host control flow); "
                "use bf16 or the interpreter engine (compiled=False)")
        from deepspeed_tpu.runtime.fp16.onebit_adam import OnebitAdam
        if isinstance(self.optimizer, OnebitAdam):
            raise ValueError(
                "compiled pipeline v1 does not support OnebitAdam: its "
                "flat error-feedback buffers don't carry the [stage, "
                "block] stacking axis, so the engine would silently shard "
                "them over the pipe axis on their first (per-worker) dim; "
                "use the interpreter engine (compiled=False) or a dense "
                "optimizer")
        if self.zero_optimization() and self.zero_optimization_stage() >= 2:
            raise ValueError(
                "compiled pipeline v1 composes PP with ZeRO stage 1 "
                "(moments sharded over each stage's data replicas); "
                "stage {} grad/param sharding is not implemented — use "
                "stage 1 or the base engine".format(
                    self.zero_optimization_stage()))
        log_dist(
            "compiled pipeline: {} prologue + {} stages x {} blocks + {} "
            "epilogue layers, gas={}".format(
                run[0], self.num_stages, self._blocks_per_stage,
                len(specs) - run[1], self.micro_batches), ranks=[0])

    # ---------------------------------------------------------- materialize

    def _cp_sharding(self, prefix_spec):
        return NamedSharding(self.mesh, prefix_spec)

    def _cp_materialize(self, x0):
        """Init prologue / stacked blocks / epilogue params by threading a
        probe micro-batch, then place them on the (pipe, data) mesh."""
        S, L = self.num_stages, self._blocks_per_stage
        i0, i1 = self._run
        tm = jax.tree_util.tree_map
        h = jnp.asarray(x0)

        # EXACTLY the interpreter engine's rng derivation (engine.py
        # _materialize) — so the two engines build identical params and
        # their trajectories are directly comparable: a threaded rng,
        # reseeded per layer (via seed_fn if given) when seed_layers.
        rng_box = [self._next_rng()]

        def init_layer(idx, layer, probe):
            rng = rng_box[0]
            if self.pipe_module.seed_layers:
                seed = self.pipe_module.base_seed + idx
                if self.pipe_module.seed_fn is not None:
                    maybe_key = self.pipe_module.seed_fn(seed)
                    rng = maybe_key if maybe_key is not None and \
                        hasattr(maybe_key, "dtype") else \
                        jax.random.PRNGKey(seed)
                else:
                    rng = jax.random.PRNGKey(seed)
            if not _is_flax_module(layer):
                rng_box[0] = rng
                return None
            # the split happens only for parameterized layers, exactly
            # like the interpreter's flax branch
            rng, sub = jax.random.split(rng)
            rng_box[0] = rng
            variables = layer.init({"params": sub, "dropout": sub}, probe)
            return variables.get("params", {})

        pro_params = []
        for idx, layer in enumerate(self._pro_layers):
            p = init_layer(idx, layer, h)
            pro_params.append(p)
            h = self._cp_apply_layer(layer, p, h)
        run_shape = h.shape

        block_params = []
        for s in range(S):
            per_stage = []
            for l in range(L):
                idx = i0 + s * L + l
                p = init_layer(idx, self._block_module, h)
                out = self._cp_apply_layer(self._block_module, p, h)
                assert out.shape == run_shape and out.dtype == h.dtype, (
                    "compiled pipeline blocks must preserve activation "
                    "shape/dtype: {} -> {}".format(run_shape, out.shape))
                h = out
                per_stage.append(p)
            block_params.append(per_stage)
        # stack: leaves [S, L, ...]
        stacked = tm(lambda *xs: jnp.stack(xs),
                     *[tm(lambda *ys: jnp.stack(ys), *ps)
                       for ps in block_params])

        epi_params = []
        for k, layer in enumerate(self._epi_layers):
            idx = i1 + k
            p = init_layer(idx, layer, h)
            epi_params.append(p)
            h = self._cp_apply_layer(layer, p, h)

        rep = self._cp_sharding(P())
        self._cp_params = {
            "prologue": jax.device_put(pro_params, rep),
            "blocks": jax.device_put(stacked,
                                     self._cp_sharding(P("pipe"))),
            "epilogue": jax.device_put(epi_params, rep),
        }
        if self.optimizer is not None:
            self._cp_opt_state = self._cp_place_state(
                self.optimizer.init_state(self._cp_params))
        self._materialized = True

    def _cp_blocks_state_sharding(self, leaf):
        """Sharding for a stacked-blocks optimizer-state leaf [S, L, ...]:
        'pipe' on the stage axis always; with ZeRO enabled, additionally
        shard the largest trailing param dim over 'data' — fp32 moments
        are the bulk of optimizer memory, and partitioning them over the
        stage's data replicas is exactly ZeRO-1 composed with PP (the
        update runs sharded; GSPMD all-gathers the new params, the same
        exchange ZeRO-1 pays)."""
        spec = [mesh_lib.PIPE_AXIS] + [None] * (leaf.ndim - 1)
        if self.zero_optimization():
            dp = self.mesh.shape.get(mesh_lib.DATA_AXIS, 1)
            if dp > 1:
                # same dim policy as mesh_lib.zero_shardings' leaf_spec
                # (first divisible dim of size >= dp), applied past the
                # [S, L] stacking prefix this engine adds.
                for d in range(2, leaf.ndim):
                    if leaf.shape[d] % dp == 0 and leaf.shape[d] >= dp:
                        spec[d] = mesh_lib.DATA_AXIS
                        break
        return self._cp_sharding(P(*spec))

    def _cp_place_state(self, st):
        """Optimizer-state leaves mirror the param tree one level down
        ({step, exp_avg{prologue,blocks,epilogue}, ...}); place the blocks
        branch on 'pipe' (+ ZeRO 'data' sharding, see above), everything
        else replicated."""
        rep = self._cp_sharding(P())
        tm = jax.tree_util.tree_map

        def place(key, val):
            if isinstance(val, dict) and "blocks" in val:
                out = {}
                for k, v in val.items():
                    if k == "blocks":
                        out[k] = tm(lambda leaf: jax.device_put(
                            leaf, self._cp_blocks_state_sharding(leaf)), v)
                    else:
                        out[k] = jax.device_put(v, rep)
                return out
            return jax.device_put(val, rep)

        return {k: place(k, v) for k, v in st.items()}

    @staticmethod
    def _cp_apply_layer(layer, params, h):
        if _is_flax_module(layer):
            return layer.apply({"params": params}, h,
                               rngs={"dropout": jax.random.PRNGKey(0)})
        return layer(h)

    # ------------------------------------------------------------- program

    def _cp_build_loss(self, dropout=True):
        """The pipelined loss program (shared by the training step and
        eval). ``dropout`` False omits every dropout rng — layers keying
        train/eval on has_rng then run deterministically, mirroring the
        interpreter's eval forwards."""
        mesh = self.mesh
        S, L, M = self.num_stages, self._blocks_per_stage, self.micro_batches
        block = self._block_module
        pro_layers, epi_layers = self._pro_layers, self._epi_layers
        loss_fn = self.pipe_module.loss_fn
        tm = jax.tree_util.tree_map
        cast = self._cast_to_compute

        def rngs_of(key):
            return {"dropout": key} if dropout else {}

        def csp(x, spec):
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, spec))

        def apply_stage(p_stage, h, rng):
            # p_stage leaves [L, ...] — the stage's blocks, applied in order.
            for l in range(L):
                pl = tm(lambda a: a[l], p_stage)
                h = block.apply({"params": pl}, h,
                                rngs=rngs_of(jax.random.fold_in(rng, l)))
            return h

        from jax import shard_map

        axis_p, axis_d = mesh_lib.PIPE_AXIS, mesh_lib.DATA_AXIS
        # No wraparound edge: stage 0 always takes the fresh micro-batch,
        # so shipping stage S-1's slab back to 0 would be pure wasted
        # traffic on the longest link; missing sources deliver zeros.
        ring = [(i, i + 1) for i in range(S - 1)]

        def worker(bp, epi_params, h, ys, rng):
            """Manual-sharding pipeline body: one pipe shard per stage,
            batch sharded over 'data'. The inter-stage handoff is an
            EXPLICIT jax.lax.ppermute riding ICI; the per-stage compute is
            the SAME function on every shard (SPMD), with this shard's
            [1, L, ...] block slice. Inside shard_map arrays are
            shard-local, so blocks launch the raw pallas flash kernels
            (attention.kernel_sharding sees the manual region)."""
            sidx = jax.lax.axis_index(axis_p)
            p_stage = tm(lambda a: a[0], bp)
            slab0 = jnp.zeros(h.shape[1:], h.dtype)   # [mb_loc, ...]
            out0 = jnp.zeros_like(h)                  # [M, mb_loc, ...]

            def tick(carry, t):
                slab, outputs = carry
                # handoff: stage s's output becomes stage s+1's input;
                # stage 0 instead ingests micro-batch t (bubble ticks
                # feed a clamped repeat whose results are masked off).
                prev = jax.lax.ppermute(slab, axis_p, ring)
                new_in = jax.lax.dynamic_index_in_dim(
                    h, jnp.clip(t, 0, M - 1), 0, keepdims=False)
                cur = jnp.where(sidx == 0, new_in, prev)
                srng = jax.random.fold_in(jax.random.fold_in(rng, t), sidx)
                cur = apply_stage(p_stage, cur, srng)
                out_idx = t - (S - 1)
                upd = jax.lax.dynamic_update_index_in_dim(
                    outputs, cur, jnp.clip(out_idx, 0, M - 1), 0)
                outputs = jnp.where((out_idx >= 0) & (sidx == S - 1),
                                    upd, outputs)
                return (cur, outputs), None

            (_, outputs), _ = jax.lax.scan(
                jax.checkpoint(tick), (slab0, out0),
                jnp.arange(M + S - 1))

            def epi(hm, ym, m):
                # Per-micro-batch dropout stream (fold the micro index, on
                # a domain disjoint from the tick/stage folds) — one
                # shared rng across the vmap would correlate every
                # micro-batch's masks, unlike the interpreter engine's
                # per-micro-batch rngs.
                erng = jax.random.fold_in(jax.random.fold_in(rng, _EPI_FOLD),
                                          m)
                for layer, p in zip(epi_layers, epi_params):
                    if _is_flax_module(layer):
                        hm = layer.apply({"params": p}, hm,
                                         rngs=rngs_of(erng))
                    else:
                        hm = layer(hm)
                if loss_fn is not None:
                    return loss_fn(hm, ym)
                return hm

            # Non-last shards ran the epilogue on zeros; only the last
            # stage's loss counts (summed over the one live shard), then
            # batch-averaged over the data axis.
            losses = jax.vmap(epi)(outputs, ys, jnp.arange(M))
            local = jnp.where(sidx == S - 1, jnp.mean(losses), 0.0)
            return jax.lax.pmean(jax.lax.psum(local, axis_p), axis_d)

        def loss_of(params, xs, ys, rng):
            params = cast(params)
            # xs: [M, mb, ...] micro-batches; prologue is data-parallel.
            # Dropout rng folds the micro-batch index (interpreter
            # engines draw a fresh rng per micro-batch forward; a shared
            # key across the vmap would reuse one mask M times).
            h = xs
            for layer, p in zip(pro_layers, params["prologue"]):
                if _is_flax_module(layer):
                    h = jax.vmap(lambda hm, m, _l=layer, _p=p: _l.apply(
                        {"params": _p}, hm,
                        rngs=rngs_of(jax.random.fold_in(
                            jax.random.fold_in(rng, _PRO_FOLD), m))))(
                                h, jnp.arange(M))
                else:
                    h = jax.vmap(layer)(h)
            h = csp(h, P(None, "data"))
            return shard_map(
                worker, mesh=mesh,
                in_specs=(P(axis_p), P(), P(None, axis_d),
                          P(None, axis_d), P()),
                out_specs=P(),
                check_vma=False)(params["blocks"], params["epilogue"],
                                 h, ys, rng)

        return loss_of

    def _cp_build_step(self):
        mesh = self.mesh
        opt = self.optimizer
        loss_of = self._cp_build_loss(dropout=True)
        clip = self.gradient_clipping()

        def step(params, opt_state, xs, ys, rng, lr, b1, b2):
            loss, grads = jax.value_and_grad(loss_of)(params, xs, ys, rng)
            if clip > 0.0:
                # global-norm clip across ALL layers, matching the
                # interpreter's optimizer step (engine.py) — inside the
                # same program, so it costs one fused reduction.
                from deepspeed_tpu.runtime.utils import clip_grad_norm_
                grads, _ = clip_grad_norm_(grads, clip)
            new_p, new_s = opt.update(params, grads, opt_state, lr=lr,
                                      betas=(b1, b2))
            return loss, new_p, new_s

        # Pin the output shardings to the materialized layouts — without
        # this GSPMD may silently replicate the ZeRO-sharded moments on
        # the first step's output and the memory saving evaporates.
        params_sh = jax.tree_util.tree_map(
            lambda x: x.sharding, self._cp_params)
        state_sh = jax.tree_util.tree_map(
            lambda x: x.sharding, self._cp_opt_state)
        return jax.jit(
            step, donate_argnums=(0, 1),
            out_shardings=(NamedSharding(mesh, P()), params_sh, state_sh))

    # --------------------------------------------------------- train_batch

    def _cp_stage_batch(self, data_iter, batch):
        """Collect gas micro-batches (from the iterator or by splitting a
        directly-passed global batch), materialize on first contact, and
        stage [M, mb, ...] onto the mesh — shared by train and eval."""
        M = self.micro_batches
        if batch is not None:
            xs0, ys0 = np.asarray(batch[0]), np.asarray(batch[1])
            assert xs0.shape[0] % M == 0
            mb = xs0.shape[0] // M
            xs = xs0.reshape((M, mb) + xs0.shape[1:])
            ys = ys0.reshape((M, mb) + ys0.shape[1:])
        else:
            micros = [next(data_iter) for _ in range(M)]
            xs = np.stack([np.asarray(m[0]) for m in micros])
            ys = np.stack([np.asarray(m[1]) for m in micros])
        if not self._materialized:
            self._cp_materialize(xs[0])
        xs = jax.device_put(xs, self._cp_sharding(P(None, "data")))
        ys = jax.device_put(ys, self._cp_sharding(P(None, "data")))
        return xs, ys

    def train_batch(self, data_iter=None, batch=None):
        assert data_iter is not None or batch is not None
        xs, ys = self._cp_stage_batch(data_iter, batch)
        if self._step_fn is None:
            self._step_fn = self._cp_build_step()
        group = self.optimizer.param_groups[0]
        lr = jnp.float32(group["lr"])
        b1, b2 = group.get("betas", (0.9, 0.999))
        loss, self._cp_params, self._cp_opt_state = self._step_fn(
            self._cp_params, self._cp_opt_state, xs, ys,
            self._next_rng(), lr, jnp.float32(b1), jnp.float32(b2))

        self.global_steps += 1
        self.global_samples += self.train_batch_size()
        if hasattr(self.optimizer, "notify_step"):
            # freeze bookkeeping (1-bit Adam): the compiled update runs
            # the degenerate pre-averaged quantization under lax.cond,
            # so no re-trace is needed at the boundary.
            self.optimizer.notify_step(self.global_steps -
                                       self.skipped_steps)
        self.agg_loss = float(loss)
        self._last_loss = self.agg_loss
        self._tensorboard_step_events()
        if self.lr_scheduler is not None:
            self.lr_scheduler.step()
        if self.global_steps % self.steps_per_print() == 0:
            self._report_progress(self.global_steps)
        return self.agg_loss

    def eval_batch(self, data_iter):
        """Pipelined evaluation: the same one-program schedule, forward
        only, with no dropout rngs (deterministic — matches the
        interpreter's eval_batch contract)."""
        if self.pipe_module.loss_fn is None:
            raise NotImplementedError(
                "compiled eval_batch needs a loss_fn (the interpreter "
                "engine's loss_fn-less eval exposes raw outputs; this "
                "engine's one-program schedule reduces to a scalar)")
        xs, ys = self._cp_stage_batch(data_iter, None)
        if getattr(self, "_eval_fn", None) is None:
            self._eval_fn = jax.jit(
                self._cp_build_loss(dropout=False),
                out_shardings=NamedSharding(self.mesh, P()))
        self.agg_loss = float(self._eval_fn(
            self._cp_params, xs, ys, jax.random.PRNGKey(0)))
        return self.agg_loss

    # ---------------------------------------------------------- checkpoint

    def _cp_unstack_tree(self, tree):
        """{'prologue': [...], 'blocks': [S, L, ...], 'epilogue': [...]}
        -> per-layer list in PipelineModule layer order — the SAME
        per-layer layout the interpreter engine uses, so the two engines'
        checkpoints interchange. Works for params and for each
        params-shaped optimizer-state branch."""
        i0, i1 = self._run
        S, L = self.num_stages, self._blocks_per_stage
        tm = jax.tree_util.tree_map
        out = [None] * len(self.pipe_module.layer_specs)
        for i, p in enumerate(tree["prologue"]):
            out[i] = p
        for s in range(S):
            for l in range(L):
                out[i0 + s * L + l] = tm(
                    lambda a, _s=s, _l=l: a[_s, _l], tree["blocks"])
        for k, p in enumerate(tree["epilogue"]):
            out[i1 + k] = p
        return out

    def _cp_restack_tree(self, per_layer):
        """Inverse of _cp_unstack_tree."""
        i0, i1 = self._run
        S, L = self.num_stages, self._blocks_per_stage
        tm = jax.tree_util.tree_map
        blocks = tm(lambda *xs: jnp.stack(xs),
                    *[tm(lambda *ys: jnp.stack(ys),
                         *[per_layer[i0 + s * L + l] for l in range(L)])
                      for s in range(S)])
        return {
            "prologue": [per_layer[i] for i in range(i0)],
            "blocks": blocks,
            "epilogue": [per_layer[i1 + k]
                         for k in range(len(per_layer) - i1)],
        }

    def _cp_unstacked(self):
        return self._cp_unstack_tree(self._cp_params)

    def _cp_per_layer_opt_states(self):
        """Optimizer state in the INTERPRETER's per-layer-list format
        (one {step, exp_avg, ...} dict per parameterized layer): scalar
        state keys are shared across layers, params-shaped keys are
        unstacked like the params."""
        per_key = {}
        for k, v in self._cp_opt_state.items():
            if isinstance(v, dict) and "blocks" in v:
                per_key[k] = self._cp_unstack_tree(v)
            else:
                per_key[k] = None  # scalar, shared
        out = []
        for i, p in enumerate(self._cp_unstacked()):
            if p is None:
                out.append(None)
                continue
            out.append({k: (self._cp_opt_state[k] if pl is None
                            else pl[i])
                        for k, pl in per_key.items()})
        return out

    def _cp_restack_opt_states(self, saved):
        """Inverse: a per-layer state list (either engine's save) back to
        the stacked full-tree state, placed on the mesh."""
        tm = jax.tree_util.tree_map
        first = next(s for s in saved if s is not None)
        st = {}
        for k, v in first.items():
            if getattr(v, "ndim", None) == 0 or np.isscalar(v):
                st[k] = jnp.asarray(v)
            else:
                per_layer = [None if s is None else
                             tm(jnp.asarray, s[k]) for s in saved]
                st[k] = self._cp_restack_tree(per_layer)
        return self._cp_place_state(st)

    def save_checkpoint(self, save_dir, tag=None, client_state=None,
                        save_latest=True):
        if tag is None:
            tag = "global_step{}".format(self.global_steps)
        ckpt_dir = os.path.join(save_dir, str(tag))
        for idx, params in enumerate(self._cp_unstacked()):
            if params is None:
                continue
            path = self.pipe_module.ckpt_layer_path(ckpt_dir, idx)
            ensure_directory_exists(path)
            with open(path, "wb") as f:
                pickle.dump(self._to_host(params), f)
        if self._cp_opt_state is not None:
            opt_path = os.path.join(
                ckpt_dir, "zero_pp_rank_0_mp_rank_00optim_states.pt")
            ensure_directory_exists(opt_path)
            with open(opt_path, "wb") as f:
                # interpreter-format per-layer list — the two engines'
                # optimizer checkpoints interchange
                pickle.dump([self._to_host(s) if s is not None else None
                             for s in self._cp_per_layer_opt_states()], f)
        self._save_ckpt_meta(ckpt_dir, save_dir, tag, client_state,
                             save_latest)
        return True

    def load_checkpoint(self, load_dir, tag=None, **kwargs):
        if tag is None:
            latest = os.path.join(load_dir, "latest")
            if not os.path.isfile(latest):
                return None, None
            with open(latest) as fd:
                tag = fd.read().strip()
        ckpt_dir = os.path.join(load_dir, str(tag))
        tm = jax.tree_util.tree_map

        def load_layer(idx):
            path = self.pipe_module.ckpt_layer_path(ckpt_dir, idx)
            if not os.path.exists(path):
                return None  # parameterless layer: save wrote no file
            with open(path, "rb") as f:
                return tm(jnp.asarray, pickle.load(f))

        per_layer = [load_layer(i)
                     for i in range(len(self.pipe_module.layer_specs))]
        if not self._materialized:
            # Canonical initialize -> load_checkpoint -> train flow: the
            # checkpointed arrays carry every shape a probe forward would
            # have produced, so materialize straight from them (no
            # train_batch needed first). Only the pipelined run's block
            # layers are required — they are all parameterized by
            # construction, so a missing file is a broken checkpoint.
            i0, i1 = self._run
            missing = [i for i in range(i0, i1) if per_layer[i] is None]
            if missing:
                raise ValueError(
                    "cannot materialize from checkpoint {}: missing "
                    "layer file(s) for pipelined block layer(s) {} "
                    "(expected {})".format(
                        ckpt_dir, missing,
                        self.pipe_module.ckpt_layer_path(ckpt_dir,
                                                         missing[0])))
        restacked = self._cp_restack_tree(per_layer)
        rep = self._cp_sharding(P())
        self._cp_params = {
            "prologue": jax.device_put(restacked["prologue"], rep),
            "blocks": jax.device_put(restacked["blocks"],
                                     self._cp_sharding(P("pipe"))),
            "epilogue": jax.device_put(restacked["epilogue"], rep),
        }
        opt_path = os.path.join(
            ckpt_dir, "zero_pp_rank_0_mp_rank_00optim_states.pt")
        loaded_opt = False
        if kwargs.get("load_optimizer_states", True) and \
                os.path.exists(opt_path):
            with open(opt_path, "rb") as f:
                saved = pickle.load(f)
            if isinstance(saved, list) and any(s is not None
                                               for s in saved):
                self._cp_opt_state = self._cp_restack_opt_states(saved)
                loaded_opt = True
        if not self._materialized:
            if not loaded_opt and self.optimizer is not None:
                # Checkpoint carried no optimizer states (or the caller
                # skipped them): fresh moments over the loaded params.
                self._cp_opt_state = self._cp_place_state(
                    self.optimizer.init_state(self._cp_params))
            self._materialized = True
        return ckpt_dir, self._load_ckpt_meta(ckpt_dir)
