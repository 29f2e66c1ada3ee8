"""DeepSpeedEngine — the core TPU training engine.

TPU-native re-design of reference runtime/engine.py:95 (DeepSpeedEngine, 1561
LoC). The public surface is preserved — ``forward`` / ``backward`` / ``step``
driven by an unchanged ds_config.json, plus checkpoint save/load — but the
execution model is JAX-first:

- ``forward(*inputs)`` runs ONE jitted ``value_and_grad`` program (forward and
  backward fused by XLA) and caches the gradients; it returns the loss, so the
  classic ``loss = engine(x); engine.backward(loss); engine.step()`` loop
  works unchanged while doing no redundant compute. The reference's per-param
  backward hooks / IPG bucket machinery (stage2.py:583-1060) vanish: gradient
  reduction is a GSPMD sharding constraint and XLA overlaps it with compute.
- ZeRO stages are sharding policies over the 'data' mesh axis
  (parallel/mesh.py:zero_shardings): stage 1 shards optimizer state AND the
  float32 master it updates, stage 2 reduce-scatters gradients
  (psum_scatter), stage 3 computes with sharded parameters. The optimizer
  update runs on each rank's shard with no collective; what a program
  computes with at stages 1-2 is the master's compute-dtype cast, gathered
  once at the program's head (``_cast_to_compute``; the fused step's region
  writes the all-gather itself, ``_dp_value_and_grad``): the reference's
  sharded allgather of updated fp16 params (stage2.py:1444-1477), a step
  later and with a backward to hide under.
- Mixed precision: fp32 master params always; compute casts to bf16 (TPU
  default) or fp16 with full DynamicLossScaler semantics (overflow-skip,
  scale-window bookkeeping — reference fp16/fused_optimizer.py).
- ``train_batch(batch)`` is the fused fast path: fwd+bwd+update in one XLA
  program with donated buffers (benchmarks use this). Where only the batch
  is split (a mesh that splits 'data' alone, ZeRO 0-2) its forward and
  backward run per chip inside one ``shard_map`` over 'data' and ZeRO's
  collectives are written, not inferred (``_dp_value_and_grad``): the
  optimizer's partition stays out of the model.
"""

import gc
import glob
import hashlib
import json
import os
import pickle
import time
import types
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.ops.adam.fused_adam import FusedAdam
from deepspeed_tpu.ops.lamb.fused_lamb import FusedLamb
from deepspeed_tpu.ops.transformer.kernels import attention as flash_kernels
from deepspeed_tpu.ops.transformer.kernels.attention import kernels_on_mesh
from deepspeed_tpu.parallel import mesh as mesh_lib
from deepspeed_tpu.runtime import lr_schedules
from deepspeed_tpu.runtime.config import (
    ADAM_OPTIMIZER,
    ADAMW_OPTIMIZER,
    DEEPSPEED_OPTIMIZERS,
    LAMB_OPTIMIZER,
    ONEBIT_ADAM_OPTIMIZER,
    DeepSpeedConfig,
)
from deepspeed_tpu.runtime.constants import ROUTE_TRAIN
from deepspeed_tpu.runtime.dataloader import DeepSpeedDataLoader, RepeatingLoader
from deepspeed_tpu.runtime.fp16.loss_scaler import CreateLossScaler
from deepspeed_tpu.runtime.progressive_layer_drop import ProgressiveLayerDrop
from deepspeed_tpu.runtime.utils import (
    clip_grad_norm_,
    ensure_directory_exists,
    has_overflow,
    jit_has_overflow,
)
from deepspeed_tpu.runtime.utils import global_norm as utils_global_norm
from deepspeed_tpu.telemetry import (MetricsRegistry, ProgramRegistry,
                                     SpanRecorder, TensorBoardScalarWriter,
                                     count_compiles_into, mark_ready,
                                     process_recorder)
from deepspeed_tpu.utils.logging import log_dist, logger
from deepspeed_tpu.utils.timer import SynchronizedWallClockTimer, ThroughputTimer


_ROOMY = []


def _traced_with_room(fn):
    """``fn()``, for a call that traces a whole step: with the cyclic
    collector paused, inside ONE frame of a megabyte.

    Both are about what a trace of 48 layers does to CPython, not to JAX
    (PERF.md, PR 46). CPython (3.11 on) keeps a thread's frames in chunks of
    16 KB that it maps when a call crosses a chunk's end and UNMAPS when the
    call returns; a trace binds 10^5 primitives, each some twenty frames up
    and down at a depth of several hundred, and wherever a chunk's end lies
    inside that stretch every bind pays an mmap, a page fault and a munmap.
    Which depth is unlucky moves with every frame above it: the data-parallel
    region's few frames took the dp4 step's trace from 15.9 to 33.0 s on the
    cell's host, and a caller's nine dummy frames had moved GPT-2's from 4.6
    to 15.2 s (PR 34). A chunk is as large as the frame that opens it needs,
    rounded up to a power of two: a frame of 2^17 + 64 slots opens one of
    2 MB and leaves the trace a megabyte in which no call crosses anything
    (minor page faults of a 12-layer trace: 28,126 -> 2,411). The collector:
    a trace allocates the step's whole jaxpr, none of it garbage until the
    trace ends, and every pass over the oldest generation walks all of it
    (11.8 -> 7.3 s on this sandbox); reference counting frees as before."""
    if not _ROOMY:
        def roomy(fn):
            return fn()

        spare = tuple("_{}".format(i) for i in range(2 ** 17 + 64))
        _ROOMY.append(types.FunctionType(roomy.__code__.replace(
            co_nlocals=1 + len(spare), co_varnames=("fn",) + spare), {}))
    was = gc.isenabled()
    gc.disable()
    try:
        return _ROOMY[0](fn)
    finally:
        if was:
            gc.enable()


class _StreamedGrads:
    """Marker for gradients that already live in the offload host buffer
    (streamed there by io_callback DURING the fused backward); carries the
    device-computed per-leaf squared norms (global-norm clipping + fp16
    overflow check) and the callback completion token — the host buffer
    MUST NOT be read before the token is fetched (sqnorms alone does not
    depend on the callbacks, so fetching it proves nothing)."""

    def __init__(self, sqnorms, token):
        self.sqnorms = sqnorms
        self.token = token


MEMORY_OPT_ALLREDUCE_SIZE = 500000000

# Debug cross-check toggle (reference stage2.py:23-25 pg_correctness_test,
# which forces deterministic fp32 allreduce so partitioned gradients can be
# compared against unpartitioned ones). TPU analog: with the flag on, every
# training fwd+bwd ALSO runs an unconstrained program (no ZeRO gradient
# sharding constraints, fully replicated batch) and asserts the sharded
# path produced the same gradients — catching partitioner/constraint bugs
# at the step they occur. Debug-only: doubles compute per step.
pg_correctness_test = False

SUMMARY_WRITER_DIR_NAME = "JobId"


def split_half_float_double_csr(tensors):
    """Bucket tensors by dtype with CSR tensors in their own bucket
    (reference engine.py:54-66, which keys off torch tensor type strings).
    TPU form: (dtype name, bucket) pairs over jnp dtypes + CSRTensor."""
    from deepspeed_tpu.runtime.csr_tensor import CSRTensor

    order = [jnp.bfloat16.dtype.name, jnp.float16.dtype.name,
             jnp.float32.dtype.name, jnp.float64.dtype.name,
             CSRTensor.type()]
    groups = {}
    for t in tensors:  # single pass
        key = CSRTensor.type() if isinstance(t, CSRTensor) \
            else jnp.asarray(t).dtype.name
        groups.setdefault(key if key in order else "other", []).append(t)
    return [(dtype, groups[dtype]) for dtype in order + ["other"]
            if dtype in groups]


class DeepSpeedEngine(object):
    """The TPU DeepSpeed engine. Wraps a flax module (or any object with
    ``init``/``apply``) and executes its training loop via jitted XLA programs
    over a device mesh."""

    def __init__(self,
                 args,
                 model,
                 optimizer=None,
                 model_parameters=None,
                 training_data=None,
                 lr_scheduler=None,
                 mpu=None,
                 dist_init_required=None,
                 collate_fn=None,
                 config_params=None,
                 dont_change_device=False,
                 mesh=None,
                 seed=1234):
        # The whole constructor is ``setup/engine_init`` of the process's
        # record of its start-up (docs/OBSERVABILITY.md).
        with process_recorder().timed("setup/engine_init",
                                      engine="training"):
            self.client_optimizer = optimizer
            self.client_model_parameters = model_parameters
            self.client_lr_scheduler = lr_scheduler
            self.training_data = training_data
            self.collate_fn = collate_fn
            self.mpu = mpu
            self.global_steps = 0
            self.global_samples = 0
            self.micro_steps = 0
            self.skipped_steps = 0
            self.gradient_average = True
            # API-parity flag (reference engine.py:369-372 reads it to skip the
            # dense allreduce). On the TPU jit path gradient reduction is a GSPMD
            # sharding decision made at trace time, so this flag is informational:
            # OnebitAdam flips it at the freeze boundary so user scripts that
            # consult it (as with the reference) observe the same transition.
            self.enable_backward_allreduce = True
            self.warn_unscaled_loss = True
            self.progressive_layer_drop = None
            self.dist_backend = "xla-ici"

            # Device mesh: the TPU-native replacement for process groups.
            self.mesh = mesh if mesh is not None else mesh_lib.build_mesh()
            self.dp_world_size = self._config_world_size()
            self.mp_world_size = mesh_lib.mp_size(self.mesh)
            self.world_size = self.dp_world_size
            self.global_rank = 0
            self.local_rank = getattr(args, "local_rank", 0) if args else 0

            # Sequence parallelism reshapes the mesh (dp x sp), which feeds the
            # batch triangle (train = micro * gas * dp) — peek at the raw config
            # BEFORE the full parse validates batch sizes.
            sp_enabled, sp_size = self._peek_sequence_parallel(args, config_params)
            if sp_enabled:
                self._setup_sequence_parallel_mesh(mesh, sp_size)

            self._config = self._configure_with_arguments(args, config_params)
            self._do_args_sanity_check(args)

            self.module = model
            self.training = True

            # RNG: pure threefry keys replace the reference's CUDA RNG tracker.
            self._rng = jax.random.PRNGKey(seed)

            # Precision policy (fp32 master params always).
            if self.amp_enabled():
                # The reference hands `amp: {...}` to apex.amp.initialize
                # (reference engine.py:569-575). The TPU-native cast policy
                # that matches apex O1/O2 semantics — mixed-precision compute
                # against fp32 master weights, no loss scaling required — is
                # bf16 compute, which this engine already implements; amp maps
                # onto it. Like the reference, amp is mutually exclusive with
                # the explicit fp16/bf16 blocks.
                if self.fp16_enabled() or self.bfloat16_enabled():
                    raise ValueError(
                        "amp is mutually exclusive with the fp16/bf16 config "
                        "blocks (reference semantics); enable exactly one")
                opt_level = dict(self.amp_params() or {}).get("opt_level", "O1")
                if opt_level not in ("O0", "O1", "O2", "O3"):
                    raise ValueError("unknown amp opt_level {!r}".format(opt_level))
                log_dist("amp enabled (opt_level {}): mapped to the bf16 "
                         "mixed-precision policy (bf16 compute, fp32 master "
                         "params)".format(opt_level), ranks=[0])
                self.compute_dtype = (jnp.float32 if opt_level == "O0"
                                      else jnp.bfloat16)
            elif self.fp16_enabled():
                self.compute_dtype = jnp.float16
            elif self.bfloat16_enabled():
                self.compute_dtype = jnp.bfloat16
            else:
                self.compute_dtype = jnp.float32

            # Telemetry registry (telemetry/): the wall_clock_breakdown
            # timers observe their phase durations into it as timer_seconds
            # histograms, the throughput timer exposes a live
            # samples_per_sec gauge, and the step/sample/lr trackers below
            # read the engine's own state at scrape time. Exporters
            # (Prometheus text, the TensorBoard scalar writer behind the
            # tensorboard_* config keys) read the same registry.
            self.telemetry = MetricsRegistry(engine="training")
            count_compiles_into(self.telemetry)
            # Where this engine stands on its way to ready: 0 no step yet, 1
            # its first step under way (``setup/first_step``), 2 ready.
            self._startup = 0
            # The span recorder the serving engine has (telemetry/tracing.py):
            # one call writes the ring and the profiler's trace under one name.
            # train_batch leaves train/step > train/shard_batch, train/dispatch,
            # train/bookkeeping; the three-call path train/forward,
            # train/backward, train/update. No span syncs the device.
            self.tracer = SpanRecorder()
            self.timers = SynchronizedWallClockTimer(registry=self.telemetry)
            self.tput_timer = ThroughputTimer(
                batch_size=self.train_micro_batch_size_per_gpu(),
                num_workers=self.dp_world_size,
                steps_per_output=self.steps_per_print(),
                monitor_memory=False,
                registry=self.telemetry)
            self.telemetry.gauge("global_steps").set_fn(
                lambda: self.global_steps)
            self.telemetry.gauge("global_samples").set_fn(
                lambda: self.global_samples)
            self.telemetry.gauge("skipped_steps").set_fn(
                lambda: self.skipped_steps)
            self.telemetry.gauge("lr").set_fn(
                lambda: (self.get_lr() if self.optimizer else [0.0])[0])
            # What flash attention's launcher resolved from the shapes of the
            # last call traced: S, the rows of the strips a diagonal block is
            # taken in (0: the block is its own tile), and the share of the
            # score tiles it computes (0.5625 at S 128 in a block of 1024).
            self.telemetry.gauge("flash_subtile").set_fn(
                lambda: flash_kernels.last_walk()["subtile"])
            self.telemetry.gauge("flash_tiles_visited_share").set_fn(
                lambda: flash_kernels.last_walk()["tiles_visited_share"])
            # ... and the heads a 128-lane tile where that call took the
            # projection's own [B, T, lanes] layout (2 at head dim 64); 0
            # where the head-major [B, H, T, d] entry ran.
            self.telemetry.gauge("flash_lane_pack").set_fn(
                lambda: flash_kernels.last_walk()["lane_pack"])
            # How the gradient leaves left the fused step last traced, where
            # its forward and backward run per chip (_dp_value_and_grad): by
            # psum_scatter onto ZeRO-2's partition, by psum; and how many
            # leaves of the sharded master's cast it gathered at its head.
            # All 0 where GSPMD partitions the step (one chip, stage 3, a
            # model / pipe / seq mesh).
            self._zero_leaves = (0, 0, 0)
            self.telemetry.gauge("zero_scatter_leaves").set_fn(
                lambda: self._zero_leaves[0])
            self.telemetry.gauge("zero_psum_leaves").set_fn(
                lambda: self._zero_leaves[1])
            self.telemetry.gauge("zero_gather_leaves").set_fn(
                lambda: self._zero_leaves[2])
            # Bytes of the master one chip holds over the whole master's
            # (_setup_shardings): 1/dp at ZeRO 1-3, 1 at stage 0, on one
            # chip and under ZeRO-Offload (the master is on the host).
            self._master_shard_share = 1.0
            self.telemetry.gauge("zero_master_shard_share").set_fn(
                lambda: self._master_shard_share)
            # Perf X-ray (telemetry/xray.py): train_batch's fused path
            # stashes each compiled step program's shape signature here
            # (microseconds; no compile). perf_xray() / the flops profiler
            # materialize the cost/memory records on demand.
            self.xray = ProgramRegistry(self.telemetry,
                                        platform=jax.default_backend())

            self.training_dataloader = self.deepspeed_io(training_data) \
                if training_data else None

            # Parameters: client-provided pytree, module attribute, or lazy-init
            # at first forward from the batch shapes.
            with process_recorder().timed("setup/params"):
                self.params = self._extract_params(model, model_parameters)

            # Loss scaling (fp16 only; bf16/fp32 need none).
            self.loss_scaler = None
            if self.fp16_enabled():
                self.loss_scaler = CreateLossScaler(
                    dynamic_scaling=self.dynamic_loss_scale(),
                    static_loss_scale=self.loss_scale() or 1.0,
                    dynamic_loss_args=self.dynamic_loss_scale_args())

            with process_recorder().timed("setup/optimizer_state"):
                self._configure_optimizer(optimizer, model_parameters)
            self._configure_lr_scheduler(lr_scheduler)

            if self.pld_enabled():
                self.progressive_layer_drop = self._configure_progressive_layer_drop()

            self._configure_checkpointing()

            # TensorBoard monitor (reference engine.py:149-150), now a
            # telemetry.TensorBoardScalarWriter (lazy; warn-once no-op when
            # the extra is missing).
            self._tb_writer = None
            self._last_loss = None

            # Jitted program caches, keyed by static call signature.
            self._fwd_bwd_cache = {}
            self._update_fn = None
            self._fused_step_cache = {}
            self._cached_grads = None
            self._grad_acc = None

            # ZeRO sharding policy (applied when params exist).
            self._shardings_ready = False
            self._grad_constraint = None
            self._compute_sharding = None
            if self.params is not None:
                self._setup_shardings()

            if self.dump_state():
                self._dump_state()

    # ------------------------------------------------------------------ config

    def _config_world_size(self):
        """Data-parallel world size used for batch-triangle math. The
        PipelineEngine overrides this (its executor is dp=1 within stages)."""
        return mesh_lib.dp_size(self.mesh)

    def _peek_sequence_parallel(self, args, config_params):
        """(enabled, size) from the raw config source, read before the
        full DeepSpeedConfig parse (see __init__)."""
        from deepspeed_tpu.runtime.config import (
            get_sequence_parallel_enabled, get_sequence_parallel_size)

        raw = config_params
        config_file = getattr(args, "deepspeed_config", None) if args \
            else None
        if raw is None and config_file and os.path.isfile(config_file):
            with open(config_file) as f:
                raw = json.load(f)
        if not isinstance(raw, dict):
            return False, None
        return (get_sequence_parallel_enabled(raw),
                get_sequence_parallel_size(raw))

    def _setup_sequence_parallel_mesh(self, user_mesh, size):
        """Rebuild/validate the mesh for sequence parallelism: the token
        dim of every batch shards over a 'seq' axis (config
        "sequence_parallel": {"enabled": true, "size": N}). With a
        user-provided mesh the axis must already exist at the right size;
        the default mesh is rebuilt as dp x sp over the same devices."""
        if user_mesh is not None:
            have = mesh_lib.sp_size(user_mesh)
            if have <= 1:
                raise ValueError(
                    "sequence_parallel is enabled but the provided mesh "
                    "has no 'seq' axis (build_mesh(num_sp=...))")
            if size is not None and size != have:
                raise ValueError(
                    "sequence_parallel size {} != mesh 'seq' axis {}"
                    .format(size, have))
            return
        n = len(jax.devices())
        if size is None:
            size = n
        if n % size:
            raise ValueError(
                "sequence_parallel size {} does not divide {} devices"
                .format(size, n))
        self.mesh = mesh_lib.build_mesh(num_sp=size, num_dp=n // size)
        self.dp_world_size = self._config_world_size()
        self.world_size = self.dp_world_size

    def _configure_with_arguments(self, args, config_params):
        config_file = getattr(args, "deepspeed_config", None) if args else None
        assert config_file is not None or config_params is not None, \
            "DeepSpeed requires --deepspeed_config to specify configuration file"
        if config_file is not None and config_params is not None:
            # Mirrors the reference sanity check (engine.py:460-474): the two
            # config sources are mutually exclusive.
            raise ValueError(
                "Not sure how to proceed, we were given both a deepspeed_config "
                "file and a config_params dict — pass exactly one")
        if config_file is not None and not os.path.isfile(config_file):
            raise FileNotFoundError(
                "DeepSpeed config file not found: {}".format(config_file))
        return DeepSpeedConfig(config_file,
                               mpu=self.mpu,
                               param_dict=config_params,
                               world_size=self.dp_world_size)

    def _do_args_sanity_check(self, args):
        if args is not None and hasattr(args, "deepscale_config") and \
                args.deepscale_config is not None:
            logger.warning(
                "************ --deepscale_config is deprecated, please use "
                "--deepspeed_config ************")
            args.deepspeed_config = args.deepscale_config

    # config getters — mirror the reference's getter battery (engine.py:204-398)
    def train_batch_size(self):
        return self._config.train_batch_size

    def train_micro_batch_size_per_gpu(self):
        return self._config.train_micro_batch_size_per_gpu

    def gradient_accumulation_steps(self):
        return self._config.gradient_accumulation_steps

    def steps_per_print(self):
        return self._config.steps_per_print

    def dump_state(self):
        return self._config.dump_state

    def wall_clock_breakdown(self):
        return self._config.wall_clock_breakdown

    def memory_breakdown(self):
        return self._config.memory_breakdown

    def sparse_gradients_enabled(self):
        return self._config.sparse_gradients_enabled

    def sequence_parallel_enabled(self):
        return self._config.sequence_parallel_enabled

    def sequence_parallel_size(self):
        return mesh_lib.sp_size(self.mesh)

    def zero_optimization(self):
        return self._config.zero_enabled

    def zero_optimization_stage(self):
        return self._config.zero_optimization_stage

    def zero_cpu_offload(self):
        return self._config.zero_config.cpu_offload

    def offload_timing(self):
        """Last _offload_step's phase timeline: stage_s (device->host wait
        + staging pack), adam_s (C++ host optimizer), upload_s (host->
        device dispatch), wall_s, chunks, and overlap_ratio = phase sum /
        wall (1.0 = fully serial; >1 = phases overlapped). None until an
        offload step has run."""
        return getattr(self, "_offload_timing", None)

    def zero_overlap_comm(self):
        return self._config.zero_config.overlap_comm

    def zero_reduce_scatter(self):
        return self._config.zero_config.reduce_scatter

    def zero_allgather_partitions(self):
        return self._config.zero_config.allgather_partitions

    def zero_reduce_bucket_size(self):
        return self._config.zero_config.reduce_bucket_size

    def zero_allgather_bucket_size(self):
        return self._config.zero_config.allgather_bucket_size

    def zero_contiguous_gradients(self):
        return self._config.zero_config.contiguous_gradients

    def zero_elastic_checkpoint(self):
        return self._config.zero_config.elastic_checkpoint

    def zero_load_from_fp32_weights(self):
        return self._config.zero_config.load_from_fp32_weights

    def fp16_enabled(self):
        return self._config.fp16_enabled

    def bfloat16_enabled(self):
        return self._config.bfloat16_enabled

    def amp_enabled(self):
        return self._config.amp_enabled

    def amp_params(self):
        return self._config.amp_params

    def loss_scale(self):
        return self._config.loss_scale

    def dynamic_loss_scale(self):
        return self._config.loss_scale == 0

    def initial_dynamic_scale(self):
        return self._config.initial_dynamic_scale

    def dynamic_loss_scale_args(self):
        return self._config.dynamic_loss_scale_args

    def gradient_clipping(self):
        return self._config.gradient_clipping

    def _warn_onebit_clip_once(self, clip):
        """One-time notice that 1-bit Adam's compression phase operates on
        UNCLIPPED local grads (the reference compression phase does too,
        but its fp16 wrapper still unscales+clips first) — a configured
        clip value stops applying past the freeze boundary. Shared by the
        base engine's shard_map hot path and the pipeline engine's
        per-stage compressed update."""
        if clip > 0.0 and not getattr(self, "_onebit_clip_warned", False):
            self._onebit_clip_warned = True
            logger.warning(
                "1-bit Adam compressed phase ignores gradient_clipping=%s: "
                "clipping applies only during warmup; the quantization "
                "scale bounds the exchanged update instead.", clip)

    def optimizer_name(self):
        return self.client_optimizer.__class__.__name__ \
            if self.client_optimizer else self._config.optimizer_name

    def optimizer_params(self):
        return self._config.optimizer_params

    def optimizer_legacy_fusion(self):
        return self._config.optimizer_legacy_fusion

    def scheduler_name(self):
        return self._config.scheduler_name

    def scheduler_params(self):
        return self._config.scheduler_params

    def tensorboard_enabled(self):
        return self._config.tensorboard_enabled

    def tensorboard_output_path(self):
        return self._config.tensorboard_output_path

    def tensorboard_job_name(self):
        return self._config.tensorboard_job_name

    def _tensorboard_log_dir(self, name="DeepSpeedJobName", base=None):
        """Event-file directory (reference engine.py:247-272): under
        <output_path>/<job_name>, or the $DLWS/DLTS job dirs."""
        if self.tensorboard_output_path():
            return os.path.join(self.tensorboard_output_path(),
                                self.tensorboard_job_name() or name)
        summary_writer_dir_name = (self.tensorboard_job_name() or name)
        if base is None:
            base = os.path.join(os.path.expanduser("~"), "tensorboard")
        if "DLWS_JOB_ID" in os.environ:
            infra_job_id = os.environ["DLWS_JOB_ID"]
        elif "DLTS_JOB_ID" in os.environ:
            infra_job_id = os.environ["DLTS_JOB_ID"]
        else:
            infra_job_id = "unknown-job-id"
        return os.path.join(base, infra_job_id, summary_writer_dir_name)

    def _scalar_writer(self, name="DeepSpeedJobName", base=None):
        """Lazy telemetry.TensorBoardScalarWriter behind the
        ``tensorboard_*`` config keys. Degrades to a warn-once no-op
        when the tensorboard extra is missing — training never crashes
        over an exporter."""
        if self._tb_writer is None:
            self._tb_writer = TensorBoardScalarWriter(
                self._tensorboard_log_dir(name=name, base=base))
        return self._tb_writer

    def get_summary_writer(self, name="DeepSpeedJobName", base=None):
        """The raw SummaryWriter (reference API); raises when the
        tensorboard extra is unavailable — callers who can proceed
        without it should go through ``_scalar_writer()`` instead."""
        writer = self._scalar_writer(name=name, base=base)._get()
        if writer is None:
            raise RuntimeError(
                "tensorboard is unavailable (torch.utils.tensorboard "
                "failed to import or the log dir is unwritable)")
        return writer

    def _tensorboard_step_events(self):
        """Per-step scalars (reference engine.py:1011-1025: Train/Samples/
        train_loss, lr, loss_scale at each boundary step), plus the
        telemetry registry snapshot (phase-timer percentiles,
        samples_per_sec, step/sample gauges) under ``telemetry/``."""
        if not self.tensorboard_enabled() or self.global_rank != 0:
            return
        tb = self._scalar_writer()
        if not tb.available:  # warned once inside the writer
            return
        if self._last_loss is not None:
            tb.add_scalar("Train/Samples/train_loss",
                          float(jax.device_get(self._last_loss)),
                          self.global_samples)
        if self.optimizer is not None:
            tb.add_scalar("Train/Samples/lr", self.get_lr()[0],
                          self.global_samples)
        if self.loss_scaler is not None:
            tb.add_scalar("Train/Samples/loss_scale",
                          self.loss_scaler.loss_scale,
                          self.global_samples)
        tb.publish(self.telemetry, self.global_samples)
        tb.flush()

    def pld_enabled(self):
        return self._config.pld_enabled

    def pld_params(self):
        return self._config.pld_params

    def pld_theta(self):
        return self.pld_params()["theta"] if self.pld_params() else 1.0

    def pld_gamma(self):
        return self.pld_params()["gamma"] if self.pld_params() else 0.001

    def postscale_gradients(self):
        return not self._config.prescale_gradients

    def gradient_predivide_factor(self):
        return self._config.gradient_predivide_factor

    def sparse_attention(self):
        return self._config.sparse_attention

    def checkpoint_tag_validation_enabled(self):
        return self._config.checkpoint_tag_validation_enabled

    def checkpoint_tag_validation_fail(self):
        return self._config.checkpoint_tag_validation_fail

    def elasticity_enabled(self):
        return self._config.elasticity_enabled

    # --------------------------------------------------------------- model/opt

    def _extract_params(self, model, model_parameters):
        if model_parameters is not None:
            # flax's init returns {'params': ...}; accept either form.
            if isinstance(model_parameters, dict) and \
                    set(model_parameters.keys()) == {"params"}:
                return model_parameters["params"]
            return model_parameters
        if hasattr(model, "params") and model.params is not None:
            return model.params
        return None

    def _cast_to_compute(self, params, gather=True):
        """The weights a program computes with: ``params`` in the compute
        dtype and, where the master is sharded for the optimizer's sake
        (ZeRO 1-2), WHOLE over 'data' again: the cast ends in a constraint to
        the layout without 'data', so a program gathers the compute copy once
        at its head and the master's split cannot propagate into a matmul.
        Not inside a ``shard_map`` over 'data' (its ``in_specs`` made the
        weights whole already), and not with ``gather`` False: the fused
        step's region gathers the local casts itself."""
        dtype = self.compute_dtype
        if dtype != jnp.float32:
            params = jax.tree_util.tree_map(
                lambda p: p.astype(dtype)
                if jnp.issubdtype(p.dtype, jnp.floating) else p, params)
        if gather and self._compute_sharding is not None and \
                mesh_lib.active_sp_axis(mesh_lib.DATA_AXIS) is None:
            # Pinned on both sides: left to itself, sharding propagation
            # makes the CONVERT whole and gathers the float32 master.
            params = jax.lax.with_sharding_constraint(
                jax.lax.with_sharding_constraint(params, self.param_sharding),
                self._compute_sharding)
        return params

    def _configure_optimizer(self, client_optimizer, model_parameters):
        if client_optimizer is not None:
            self.optimizer = client_optimizer
            log_dist("Using client Optimizer as basic optimizer", ranks=[0])
            if self.zero_cpu_offload() and not self._offload_mode():
                logger.warning(
                    "zero_optimization.cpu_offload is set but the client "
                    "optimizer is not DeepSpeedCPUAdam — optimizer state "
                    "stays in HBM (no offload).")
        elif self._config.optimizer_name is not None:
            self.optimizer = self._configure_basic_optimizer(model_parameters)
            log_dist("Using DeepSpeed Optimizer param name {} as basic optimizer"
                     .format(self.optimizer_name()), ranks=[0])
        else:
            self.optimizer = None
            return

        self.opt_state = None
        self._offload = None  # host-state bookkeeping (ZeRO-Offload tier)
        self._offload_pre_fn = None  # jitted device-side unscale+clip
        self._embed_paths_cache = None  # sparse-grad embedding leaf paths
        if self.params is not None and not self._offload_mode():
            self.opt_state = self.optimizer.init_state(self.params)

    def _offload_mode(self):
        from deepspeed_tpu.ops.adam.cpu_adam import DeepSpeedCPUAdam
        from deepspeed_tpu.ops.lamb.cpu_lamb import DeepSpeedCPULamb
        return isinstance(self.optimizer, (DeepSpeedCPUAdam,
                                           DeepSpeedCPULamb))

    def _configure_basic_optimizer(self, model_parameters):
        """Optimizer factory table (reference engine.py:577-617)."""
        optimizer_parameters = dict(self.optimizer_params() or {})
        optimizer_parameters.pop("torch_adam", None)
        optimizer_parameters.pop("adam_w_mode", None)
        name = self._config.optimizer_name
        if name in [ADAM_OPTIMIZER, ADAMW_OPTIMIZER]:
            adam_w_mode = (name == ADAMW_OPTIMIZER) or \
                (self.optimizer_params() or {}).get("adam_w_mode", name == ADAMW_OPTIMIZER)
            if self.zero_cpu_offload():
                # ZeRO-Offload decision matrix (reference engine.py:577-617):
                # cpu_offload selects DeepSpeedCPUAdam; optimizer state lives
                # in host DRAM and the update runs in the C++ op.
                from deepspeed_tpu.ops.adam.cpu_adam import DeepSpeedCPUAdam
                return DeepSpeedCPUAdam(model_params=model_parameters,
                                        adamw_mode=adam_w_mode,
                                        **optimizer_parameters)
            return FusedAdam(params=model_parameters,
                             adam_w_mode=adam_w_mode,
                             **optimizer_parameters)
        elif name == LAMB_OPTIMIZER:
            if self.zero_cpu_offload():
                # Host LAMB tier (the reference's offload matrix is
                # Adam-only, engine.py:577-617; on the TPU-VM host tier
                # LAMB composes the same way via csrc/lamb/cpu_lamb.cpp).
                from deepspeed_tpu.ops.lamb.cpu_lamb import DeepSpeedCPULamb
                host_keys = ("lr", "bias_correction", "betas", "eps",
                             "weight_decay", "max_coeff", "min_coeff",
                             "amsgrad")
                dropped = [k for k in optimizer_parameters
                           if k not in host_keys]
                if dropped:
                    # Device-only knobs (eps_inside_sqrt, max_grad_norm):
                    # warn, don't silently change semantics.
                    logger.warning(
                        "Lamb params %s are not supported by the host "
                        "(cpu_offload) tier and are ignored", dropped)
                return DeepSpeedCPULamb(
                    model_params=model_parameters,
                    **{k: v for k, v in optimizer_parameters.items()
                       if k in host_keys})
            return FusedLamb(params=model_parameters, **optimizer_parameters)
        elif name == ONEBIT_ADAM_OPTIMIZER:
            if self.zero_cpu_offload():
                raise ValueError(
                    "zero_optimization.cpu_offload requires an Adam/AdamW "
                    "optimizer (got {}); the host tier is DeepSpeedCPUAdam"
                    .format(name))
            from deepspeed_tpu.runtime.fp16.onebit_adam import OnebitAdam
            return OnebitAdam(params=model_parameters, deepspeed=self,
                              **optimizer_parameters)
        else:
            if not self._config.zero_allow_untested_optimizer and \
                    self.zero_optimization():
                raise ValueError(
                    "ZeRO with untested optimizer '{}' requires "
                    "zero_allow_untested_optimizer".format(name))
            raise ValueError("Unknown optimizer: {}".format(name))

    def _configure_lr_scheduler(self, client_lr_scheduler):
        """Config scheduler takes precedence unless client passed one
        (reference engine.py:400-446)."""
        scheduler_name = self.scheduler_name()
        if scheduler_name is not None and self.optimizer is not None:
            scheduler = getattr(lr_schedules, scheduler_name, None)
            assert scheduler is not None, \
                "DeepSpeed does not recognize LR scheduler {}".format(scheduler_name)
            scheduler_params = self.scheduler_params() or {}
            self.lr_scheduler = scheduler(self.optimizer, **scheduler_params)
            log_dist("DeepSpeed using configured LR scheduler = {}".format(
                scheduler_name), ranks=[0])
        else:
            if callable(client_lr_scheduler) and self.optimizer is not None:
                self.lr_scheduler = client_lr_scheduler(self.optimizer)
            else:
                self.lr_scheduler = client_lr_scheduler
        log_dist("DeepSpeed LR Scheduler = {}".format(self.lr_scheduler), ranks=[0])

    def _configure_checkpointing(self):
        """Push an explicit activation_checkpointing config block into the
        module-level checkpointing state. TPU-build convenience: the reference
        leaves configure() to the user (Megatron calls it); here ds_config is
        the single source of truth, but only when the block is present — a
        user's earlier direct configure() call is never clobbered."""
        from deepspeed_tpu.runtime.activation_checkpointing.config import ACT_CHKPT
        if ACT_CHKPT not in (self._config._param_dict or {}):
            return
        from deepspeed_tpu.runtime.activation_checkpointing import checkpointing
        cfg = self._config.activation_checkpointing_config
        checkpointing.configure(
            mpu_=self.mpu,
            partition_activations=cfg.partition_activations,
            contiguous_checkpointing=cfg.contiguous_memory_optimization,
            num_checkpoints=cfg.number_checkpoints,
            checkpoint_in_cpu=cfg.cpu_checkpointing,
            synchronize=cfg.synchronize_checkpoint_boundary,
            profile=cfg.profile,
            mesh_=self.mesh)

    def _configure_progressive_layer_drop(self):
        return ProgressiveLayerDrop(theta=self.pld_theta(), gamma=self.pld_gamma())

    def _setup_shardings(self):
        self._embed_paths_cache = None  # params (re)set: recompute lazily
        stage = self.zero_optimization_stage() if self.zero_optimization() else 0
        tp_rules = getattr(self.module, "tp_rules", None)
        on_chips = not self._offload_mode()
        self.param_sharding, self.grad_sharding, opt_fn = \
            mesh_lib.zero_shardings(
                self.mesh, self.params, stage, tp_rules=tp_rules,
                master_on_chips=on_chips)
        # ZeRO 1-2 split the master for the update alone: the layout a
        # program computes in is the master's without 'data'
        # (_cast_to_compute). Stage 3 computes on the shards themselves.
        self._compute_sharding = None
        if on_chips and 1 <= stage <= 2 and mesh_lib.dp_size(self.mesh) > 1:
            self._compute_sharding = mesh_lib.zero_shardings(
                self.mesh, self.params, 0, tp_rules=tp_rules)[0]
        leaves = jax.tree_util.tree_leaves(self.params)
        held = sum(np.prod(sh.shard_shape(p.shape)) for p, sh in zip(
            leaves, jax.tree_util.tree_leaves(self.param_sharding)))
        self._master_shard_share = float(
            held / max(sum(np.prod(p.shape) for p in leaves), 1))
        if self.opt_state is not None and not self._offload_mode():
            moment_sh = {
                "step": mesh_lib.replicated(self.mesh),
                "exp_avg": opt_fn(self.opt_state["exp_avg"]),
                "exp_avg_sq": opt_fn(self.opt_state["exp_avg_sq"]),
            }
            # Extra optimizer state (e.g. OnebitAdam error-feedback buffers)
            # follows the same ZeRO policy as the moments — error buffers are
            # elementwise state and must not stay replicated under ZeRO.
            for key in self.opt_state:
                if key not in moment_sh:
                    moment_sh[key] = opt_fn(self.opt_state[key])
            if self._onebit_spmd_eligible():
                # Per-worker error-feedback rows live with their worker:
                # row r is rank r's private state in the two-phase
                # exchange (compressed_allreduce), so the leading [W] dim
                # shards over 'data' and the shard_map hot path sees only
                # its own row.
                row_sh = mesh_lib.NamedSharding(
                    self.mesh, mesh_lib.P(mesh_lib.DATA_AXIS))
                for key in ("worker_error", "server_error"):
                    if key in self.opt_state:
                        moment_sh[key] = jax.tree_util.tree_map(
                            lambda _: row_sh, self.opt_state[key])
            self.opt_state_sharding = moment_sh
            # Place state according to policy now (one-time reshard).
            with process_recorder().timed("setup/optimizer_state"):
                self.opt_state = jax.device_put(self.opt_state, moment_sh)
        with process_recorder().timed("setup/params"):
            self.params = jax.device_put(self.params, self.param_sharding)
        # ZeRO-2/3 semantics (reference stage2.py:675-738): gradients are
        # REDUCE-SCATTERED to their owner shard, never materialized
        # replicated. Enforced as a GSPMD constraint inside every grad-
        # producing program (XLA lowers the cross-replica sum to
        # reduce-scatter instead of all-reduce), but for the fused step's
        # data-parallel region, which writes the psum_scatter itself.
        self._grad_constraint = self.grad_sharding if stage >= 2 else None
        self._shardings_ready = True

    # -------------------------------------------------------------- start-up

    def _first_step_begins(self):
        """``setup/first_step`` of the process's record of its start-up
        begins with the first ``train_batch`` / ``forward``: the call in
        which the step's program is traced, lowered, compiled or loaded, and
        run once. A stamp, and gone from the stack before the dispatch: one
        more local or ``with`` item in a function that IS on the stack then
        moves every frame beneath it (``_traced_with_room``; PERF.md, PR 53)."""
        if not self._startup:
            self._startup = 1
            self._first_step_began = time.time()

    def _mark_ready(self):
        """``setup/first_step`` ends (recorded after the fact) and
        ``setup/ready``, once: the first fused step (or the first ``step()``
        of the three-call path) has returned."""
        if self._startup == 1:
            self._startup = 2
            process_recorder().span("setup/first_step",
                                    self._first_step_began,
                                    engine="training")
            mark_ready("training")

    # ------------------------------------------------------------------- RNG

    def _next_rng(self):
        self._rng, sub = jax.random.split(self._rng)
        return sub

    # ------------------------------------------------------------ data loading

    def deepspeed_io(self,
                     dataset,
                     batch_size=None,
                     route=ROUTE_TRAIN,
                     pin_memory=True,
                     data_sampler=None,
                     collate_fn=None,
                     num_local_io_workers=None):
        """Build the sharded dataloader (reference engine.py:706-747).

        Single-controller JAX: one loader yields the GLOBAL micro-batch
        (micro_batch_per_chip × dp_size); the engine shards it over the 'data'
        mesh axis at dispatch.
        """
        if batch_size is None:
            batch_size = self.train_micro_batch_size_per_gpu() * self.dp_world_size
        collate_fn = collate_fn or self.collate_fn
        return DeepSpeedDataLoader(dataset=dataset,
                                   batch_size=batch_size,
                                   local_rank=self.local_rank,
                                   data_parallel_world_size=1,
                                   data_parallel_rank=0,
                                   collate_fn=collate_fn,
                                   num_local_io_workers=num_local_io_workers,
                                   data_sampler=data_sampler)

    # -------------------------------------------------------------- train/eval

    def train(self, mode=True):
        self.warn_unscaled_loss = True
        self.training = mode

    def eval(self):
        self.warn_unscaled_loss = True
        self.training = False

    def __call__(self, *inputs, **kwargs):
        return self.forward(*inputs, **kwargs)

    # --------------------------------------------------------------- forward

    def _lazy_init(self, inputs, kwargs):
        """No parameters were given: initialise them from the sharded batch's
        shapes (flax idiom; the reference gets params from the constructed
        torch module instead), as ONE compiled program. The host tier's
        optimizer builds its own state (``_init_offload``)."""
        static_kwargs, traced_kwargs = self._split_kwargs(kwargs)
        rngs = {"params": self._next_rng(), "dropout": self._next_rng()}

        def init(rngs, inputs, traced_kwargs):
            return self.module.init(rngs, *inputs, **traced_kwargs,
                                    **static_kwargs)["params"]

        self.params = jax.jit(init)(rngs, inputs, traced_kwargs)
        if self.optimizer is not None and not self._offload_mode():
            self.opt_state = self.optimizer.init_state(self.params)
        self._setup_shardings()

    def _split_kwargs(self, kwargs):
        """Traced (numeric) vs static (bool/str/None) kwargs for jit caching."""
        static, traced = {}, {}
        for k, v in kwargs.items():
            if isinstance(v, bool) or isinstance(v, str) or v is None:
                static[k] = v
            elif isinstance(v, (int, float)):
                traced[k] = jnp.asarray(v)
            else:
                traced[k] = v
        return static, traced

    def _embedding_grad_paths(self):
        """Leaf paths of embedding tables (flax nn.Embed 'embedding' params)
        — the analogue of the reference's nn.Embedding scan
        (engine.py:180-185) that decides which grads go through the sparse
        index/value exchange."""
        if self.params is None:
            return frozenset()
        if self._embed_paths_cache is not None:
            return self._embed_paths_cache
        # flax nn.Embed stores its table as '<module>/embedding'; the repo's
        # own models use raw params 'wte' (gpt2.py:149) and BERT-style
        # '*_embeddings' modules. Tables whose grads turn out dense at
        # runtime (tied softmax heads) fall back inside
        # sparse_grad_exchange, so a broad match is safe.
        embed_names = {"embedding", "wte", "word_embeddings"}
        paths = []
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                self.params)[0]:
            names = [str(getattr(p, "key", getattr(p, "name", "")))
                     for p in path]
            if getattr(leaf, "ndim", 0) >= 2 and any(
                    n in embed_names or n.endswith("_embeddings")
                    for n in names):
                paths.append(tuple(str(p) for p in path))
        self._embed_paths_cache = frozenset(paths)
        return self._embed_paths_cache

    def _get_fwd_bwd(self, n_args, static_kwargs, traced_keys, train):
        sparse_embed = bool(
            train and self.sparse_gradients_enabled()
            and mesh_lib.dp_size(self.mesh) > 1
            and self._embedding_grad_paths())
        sp_parallel = bool(self.sequence_parallel_enabled()
                           and mesh_lib.sp_size(self.mesh) > 1
                           and not getattr(self, "_force_serial_fwd_bwd",
                                           False))
        if sp_parallel and sparse_embed:
            raise NotImplementedError(
                "sequence_parallel cannot be combined with sparse_gradients")
        key = (n_args, tuple(sorted(static_kwargs.items())),
               tuple(sorted(traced_keys)), train, self.compute_dtype.__name__,
               self._grad_constraint is not None, sparse_embed, sp_parallel)
        if key in self._fwd_bwd_cache:
            return self._fwd_bwd_cache[key]
        grad_constraint = self._grad_constraint

        cast = self._cast_to_compute
        setup = self._module_apply_setup()
        apply_fn, accepts_deterministic = setup
        make_loss = self._make_loss_fn(static_kwargs, train, setup=setup)

        def loss_and_grads(params, args, traced_kwargs, rng, scale):
            loss_fn = make_loss(args, traced_kwargs, rng, scale)
            (_, out), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
            if grad_constraint is not None:
                grads = jax.lax.with_sharding_constraint(grads, grad_constraint)
            return out, grads

        if sparse_embed:
            jitted = self._build_sparse_grad_fwd_bwd(
                static_kwargs=static_kwargs, cast=cast, apply_fn=apply_fn,
                accepts_deterministic=accepts_deterministic,
                grad_constraint=grad_constraint)
        elif sp_parallel:
            jitted = self._build_sequence_parallel_fwd_bwd(
                static_kwargs=static_kwargs, cast=cast, apply_fn=apply_fn,
                accepts_deterministic=accepts_deterministic,
                grad_constraint=grad_constraint, train=train)
        else:
            jitted = jax.jit(loss_and_grads)
        self._fwd_bwd_cache[key] = jitted
        return jitted

    def _module_apply_setup(self):
        """(apply_fn, accepts_deterministic) for the wrapped module —
        shared by every fwd+bwd program builder. Training must actually
        enable dropout: flax modules gate it on a `deterministic` kwarg
        defaulting True, so builders pass False when the model accepts it
        and the caller didn't choose explicitly."""
        module = self.module
        apply_fn = module.apply if hasattr(module, "apply") else module
        accepts_deterministic = False
        try:
            import inspect
            accepts_deterministic = "deterministic" in \
                inspect.signature(type(module).__call__).parameters
        except (TypeError, ValueError):
            pass
        return apply_fn, accepts_deterministic

    def _make_loss_fn(self, static_kwargs, train, setup=None):
        """Factory for the scaled-loss closure shared by the plain and
        grad-streaming fwd+bwd builders — ONE place owns the module
        call / rng / deterministic conventions. ``setup`` lets a caller
        that already ran _module_apply_setup pass it through."""
        cast = self._cast_to_compute
        apply_fn, accepts_deterministic = setup or self._module_apply_setup()
        mesh = self.mesh

        def make(args, traced_kwargs, rng, scale):
            def loss_fn(p):
                cp = cast(p)
                call_kwargs = dict(static_kwargs)
                call_kwargs.update(traced_kwargs)
                if train:
                    if accepts_deterministic:
                        call_kwargs.setdefault("deterministic", False)
                    call_kwargs["rngs"] = {"dropout": rng}
                # The model's Pallas kernels launch shard-local over the
                # engine's mesh (entered here, inside what gets jitted).
                with kernels_on_mesh(mesh):
                    out = apply_fn({"params": cp}, *args, **call_kwargs)
                loss = out[0] if isinstance(out, tuple) else out
                return loss * scale, out

            return loss_fn

        return make

    def _stream_grads_active(self):
        """True when the offload tier should stream gradients to host
        during backward instead of materializing the full grad tree."""
        return self._offload_mode() and \
            bool(getattr(self._config.zero_config, "stream_gradients",
                         False))

    def _stream_sink(self, idx, g):
        """io_callback target: write one gradient leaf into the host
        staging buffer (fp32, master layout). Leaves occupy disjoint
        spans, so unordered callbacks may land concurrently."""
        off = self._offload
        i = int(idx)
        o, size = int(off["offsets"][i]), off["sizes"][i]
        off["stream_g"][o:o + size] = np.asarray(g, np.float32).ravel()
        return np.int32(0)

    def _get_streaming_fwd_bwd(self, n_args, static_kwargs, traced_keys,
                               train):
        """fwd+bwd program for the grad-streaming offload tier.

        The gradient tree never becomes program OUTPUT: each leaf is
        consumed inside the program by an io_callback that copies it to
        the host staging buffer, so XLA can free it as the backward
        proceeds, and the param inputs are donated (they are
        re-materialized from the host master at step() anyway). Device
        peak drops from ~4 bytes/param (bf16 params + full bf16 grad
        outputs) toward ~2 — the reference's ZeRO-Offload streams grad
        buckets to pinned CPU memory during backward for the same reason
        (stage2.py:740-817). Only per-leaf squared norms leave the
        program (clipping + overflow)."""
        key = ("stream", n_args, tuple(sorted(static_kwargs.items())),
               tuple(sorted(traced_keys)), train)
        if key in self._fwd_bwd_cache:
            return self._fwd_bwd_cache[key]
        from jax.experimental import io_callback

        make_loss = self._make_loss_fn(static_kwargs, train)
        sink = self._stream_sink

        def loss_and_stream(params, args, traced_kwargs, rng, scale):
            loss_fn = make_loss(args, traced_kwargs, rng, scale)
            _, vjp_fn, out = jax.vjp(loss_fn, params, has_aux=True)
            (grads,) = vjp_fn(jnp.float32(1.0))
            sqs, toks = [], []
            for i, g in enumerate(jax.tree_util.tree_leaves(grads)):
                sqs.append(jnp.sum(g.astype(jnp.float32) ** 2))
                # Unordered: leaves write disjoint host spans. The token
                # is folded into an output so DCE keeps the callback.
                toks.append(io_callback(
                    sink, jax.ShapeDtypeStruct((), jnp.int32),
                    jnp.int32(i), g))
            return out, jnp.stack(sqs), jnp.stack(toks).sum()

        jitted = jax.jit(loss_and_stream, donate_argnums=0)
        self._fwd_bwd_cache[key] = jitted
        return jitted

    def _build_sequence_parallel_fwd_bwd(self, static_kwargs, cast, apply_fn,
                                         accepts_deterministic,
                                         grad_constraint, train):
        """fwd+bwd program with SEQUENCE parallelism: tokens shard over the
        'seq' mesh axis under shard_map; the model runs on its local token
        slice (ring attention mixes across shards — the model must be
        sequence-shardable, e.g. GPT2Config(sequence_parallel_axis='seq')),
        grads psum over 'seq' and pmean over 'data'. Beyond the reference
        (v0.3.10 has no sequence parallelism, SURVEY §0)."""
        from functools import partial

        from jax import shard_map

        mesh = self.mesh
        dp = mesh_lib.dp_size(mesh)
        sp = mesh_lib.sp_size(mesh)
        module_cfg = getattr(self.module, "config", None)
        if getattr(module_cfg, "sequence_parallel_axis", None) != \
                mesh_lib.SEQ_AXIS:
            raise ValueError(
                "sequence_parallel is enabled but the model is not "
                "sequence-shardable: its config must set "
                "sequence_parallel_axis='{}' (attention must mix tokens "
                "across shards — silently sharding a serial model would "
                "train a different function)".format(mesh_lib.SEQ_AXIS))

        def loss_and_grads(params, args, traced_kwargs, rng, scale):
            P_ = jax.sharding.PartitionSpec

            def check(x):
                # Silent down-sharding would run the model's SP paths on
                # wrong decompositions (full sequences treated as shards,
                # or non-token dims sliced): every batch array must split
                # exactly — batch over dp, and tokens (dim 1 of any rank>=2
                # array) over sp.
                shape = getattr(x, "shape", ())
                if len(shape) >= 1 and shape[0] % dp:
                    raise ValueError(
                        "sequence_parallel: batch dim {} of shape {} not "
                        "divisible by dp={}".format(shape[0], shape, dp))
                if len(shape) >= 2 and shape[1] % sp:
                    raise ValueError(
                        "sequence_parallel: token dim {} of shape {} not "
                        "divisible by sp={} (all rank>=2 batch arrays are "
                        "token-sharded on dim 1)".format(
                            shape[1], shape, sp))
                return x

            jax.tree_util.tree_map(check, (args, traced_kwargs))

            def arg_spec(x):
                return mesh_lib.batch_partition_spec(x, dp, sp)

            arg_specs = jax.tree_util.tree_map(arg_spec, args)
            kw_specs = jax.tree_util.tree_map(arg_spec, traced_kwargs)

            @partial(shard_map, mesh=mesh,
                     in_specs=(P_(), arg_specs, kw_specs, P_(), P_()),
                     out_specs=(P_(), P_()), check_vma=False)
            def spmd(params, largs, lkwargs, rng, scale):
                rng = jax.random.fold_in(
                    rng, jax.lax.axis_index(mesh_lib.DATA_AXIS) * sp
                    + jax.lax.axis_index(mesh_lib.SEQ_AXIS))

                def loss_fn(p):
                    cp = cast(p)
                    call_kwargs = dict(static_kwargs)
                    call_kwargs.update(lkwargs)
                    if train and accepts_deterministic:
                        call_kwargs.setdefault("deterministic", False)
                    rngs = {"dropout": rng} if train else {}
                    out = apply_fn({"params": cp}, *largs,
                                   rngs=rngs, **call_kwargs)
                    if isinstance(out, tuple):
                        raise NotImplementedError(
                            "sequence_parallel requires the model to "
                            "return the scalar loss (auxiliary outputs "
                            "would be silently dropped)")
                    return out * scale, out

                (_, out), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params)
                # The model's internal psum already made the loss uniform
                # over 'seq'; average over 'data' for the global batch mean.
                out = jax.lax.pmean(out, mesh_lib.DATA_AXIS)
                # shard_map autodiff is collective-aware: differentiating
                # THROUGH the model's psum/ppermute ties the shards, so
                # each device's grad is already the FULL gradient of its
                # data-shard's loss (psum's transpose is psum) — pmean
                # over 'seq' (deduplicate), pmean over 'data' (global
                # batch mean). A psum over 'seq' here would scale grads by
                # sp — invisible to Adam (scale-invariant) but wrong for
                # clipping/SGD; pg_correctness_test now guards this
                # against the forced-serial reference.
                grads = jax.tree_util.tree_map(
                    lambda g: jax.lax.pmean(
                        jax.lax.pmean(g, mesh_lib.SEQ_AXIS),
                        mesh_lib.DATA_AXIS),
                    grads)
                return out, grads

            out, grads = spmd(params, args, traced_kwargs, rng, scale)
            if grad_constraint is not None:
                grads = jax.lax.with_sharding_constraint(
                    grads, grad_constraint)
            return out, grads

        return jax.jit(loss_and_grads)

    def _build_sparse_grad_fwd_bwd(self, static_kwargs, cast, apply_fn,
                                   accepts_deterministic, grad_constraint):
        """fwd+bwd program with SPARSE embedding-gradient exchange: the loss
        is computed per data shard under shard_map, dense grads are psum'd,
        and embedding-table grads are exchanged as (row-index, row-value)
        pairs bounded by the shard's token count (reference CSR sparse-grad
        DP, engine.py:180-185,1186-1242)."""
        from functools import partial

        from jax import shard_map

        from deepspeed_tpu.runtime.csr_tensor import sparse_grad_exchange

        mesh = self.mesh
        dp = mesh_lib.dp_size(mesh)
        embed_paths = self._embedding_grad_paths()

        def loss_and_grads(params, args, traced_kwargs, rng, scale):
            def batch_spec(x):
                return mesh_lib.batch_partition_spec(x, dp)

            arg_specs = jax.tree_util.tree_map(batch_spec, args)
            kw_specs = jax.tree_util.tree_map(batch_spec, traced_kwargs)
            P_ = jax.sharding.PartitionSpec

            @partial(shard_map, mesh=mesh,
                     in_specs=(P_(), arg_specs, kw_specs, P_(), P_()),
                     out_specs=(P_(), P_()), check_vma=False)
            def spmd(params, largs, lkwargs, rng, scale):
                rng = jax.random.fold_in(
                    rng, jax.lax.axis_index(mesh_lib.DATA_AXIS))

                def loss_fn(p):
                    cp = cast(p)
                    call_kwargs = dict(static_kwargs)
                    call_kwargs.update(lkwargs)
                    if accepts_deterministic:
                        call_kwargs.setdefault("deterministic", False)
                    out = apply_fn({"params": cp}, *largs,
                                   rngs={"dropout": rng}, **call_kwargs)
                    if isinstance(out, tuple):
                        # Loud, not silent: the sparse path returns only the
                        # pmean'd scalar, so auxiliary outputs would be
                        # dropped behind the user's back.
                        raise NotImplementedError(
                            "sparse_gradients with data parallelism "
                            "requires a scalar-loss model output; this "
                            "model returns a tuple — disable "
                            "sparse_gradients or return only the loss")
                    loss = out
                    assert getattr(loss, "ndim", 0) == 0, \
                        "sparse_gradients requires a scalar loss output"
                    return loss * scale, loss

                (_, loss), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params)
                # Token budget = this shard's integer elements (ids+labels):
                # an embedding grad has at most one nonzero row per token.
                k = sum(int(np.prod(l.shape)) for l in
                        jax.tree_util.tree_leaves((largs, lkwargs))
                        if jnp.issubdtype(l.dtype, jnp.integer)) or None

                def reduce_leaf(path, g):
                    names = tuple(str(p) for p in path)
                    if names in embed_paths and k is not None:
                        return sparse_grad_exchange(
                            g, mesh_lib.DATA_AXIS, k, average=True)
                    return jax.lax.pmean(g, mesh_lib.DATA_AXIS)

                grads = jax.tree_util.tree_map_with_path(reduce_leaf, grads)
                loss = jax.lax.pmean(loss, mesh_lib.DATA_AXIS)
                return loss, grads

            loss, grads = spmd(params, args, traced_kwargs, rng, scale)
            if grad_constraint is not None:
                grads = jax.lax.with_sharding_constraint(grads,
                                                         grad_constraint)
            return loss, grads

        return jax.jit(loss_and_grads)

    def forward(self, *inputs, **kwargs):
        """Run forward AND backward as one fused XLA program; cache grads.

        Returns the module output (the loss, by DeepSpeed convention). The
        cached grads are consumed by :meth:`backward`.
        """
        self._first_step_begins()
        if self.flops_profiler_enabled() and \
                self.global_steps == self.flops_profiler_start_step() and \
                self.global_rank == 0:
            self._start_flops_profiler()

        if self.progressive_layer_drop:
            kwargs.update(self.progressive_layer_drop.get_state())

        if self.wall_clock_breakdown():
            self.timers("forward_microstep").start()
            self.timers("forward").start()

        inputs = tuple(jnp.asarray(x) if isinstance(x, np.ndarray) else x
                       for x in inputs)
        inputs = mesh_lib.shard_batch(self.mesh, inputs)

        if self.params is None:
            self._lazy_init(inputs, kwargs)

        if self.training:
            self.tput_timer.start()

        static_kwargs, traced_kwargs = self._split_kwargs(kwargs)
        scale = jnp.float32(self.loss_scaler.loss_scale) if self.loss_scaler \
            else jnp.float32(1.0)
        step_rng = self._next_rng()
        if self.training and self._stream_grads_active():
            assert self.gradient_accumulation_steps() == 1, \
                "stream_gradients requires gradient_accumulation_steps=1 " \
                "(params are donated per backward)"
            assert len(self.mesh.devices.flat) == 1, \
                "stream_gradients targets single-chip offload capacity; " \
                "use plain cpu_offload on multi-device meshes"
            assert not pg_correctness_test, \
                "pg_correctness_test needs materialized gradients — " \
                "disable stream_gradients to cross-check"
            if self._offload is None:
                self._init_offload()
            if "stream_g" not in self._offload:
                self._offload["stream_g"] = np.empty(
                    int(self._offload["master"].size), np.float32)
            fwd_bwd = self._get_streaming_fwd_bwd(
                len(inputs), static_kwargs, traced_kwargs.keys(),
                self.training)
            with self.tracer.timed("train/forward",
                                   step_num=self.global_steps):
                out, sqnorms, token = fwd_bwd(self.params, inputs,
                                              traced_kwargs, step_rng, scale)
            self._cached_grads = _StreamedGrads(sqnorms, token)
            if self.wall_clock_breakdown():
                self.timers("forward").stop(wait_for=(out, sqnorms))
                self.timers("forward_microstep").stop()
            return out
        fwd_bwd = self._get_fwd_bwd(len(inputs), static_kwargs,
                                    traced_kwargs.keys(), self.training)
        with self.tracer.timed("train/forward", step_num=self.global_steps):
            out, grads = fwd_bwd(self.params, inputs, traced_kwargs,
                                 step_rng, scale)
        if pg_correctness_test and self.training:
            self._pg_correctness_check(inputs, static_kwargs, traced_kwargs,
                                       step_rng, scale, grads)
        if getattr(self, "flops_profiler", None) is not None and \
                self.flops_profiler.started:
            # Exact program cost from XLA (fwd+bwd in one program); the
            # example batch feeds the per-module tabulation report.
            if self.flops_profiler._example_args is None:
                self.flops_profiler.set_example_batch(*inputs)
            # Constant key: observe() only needs shapes/dtypes for lowering;
            # splitting the engine RNG here would make profiling perturb
            # training.
            self.flops_profiler.observe(fwd_bwd, self.params, inputs,
                                        traced_kwargs,
                                        jax.random.PRNGKey(0), scale)
        if self.training:
            self._cached_grads = grads

        if self.wall_clock_breakdown():
            # The opt-in breakdown waits for what each phase produced
            # (utils/timer.py): forward + backward are one program here.
            self.timers("forward").stop(wait_for=(out, grads))
            self.timers("forward_microstep").stop()

        if self.flops_profiler_enabled() and \
                self.global_steps == self.flops_profiler_end_step() and \
                self.global_rank == 0:
            self._stop_flops_profiler()

        return out

    def _pg_correctness_check(self, inputs, static_kwargs, traced_kwargs,
                              rng, scale, sharded_grads):
        """Cross-check sharded-path gradients against an INDEPENDENT
        reference program: fp32 compute, no ZeRO sharding constraints,
        fully replicated data (reference pg_correctness_test,
        stage2.py:23-25: deterministic fp32 allreduce so partitioned grads
        can be verified against unpartitioned ones). Forcing fp32 keeps the
        reference program distinct even at stage 0/1, where the sharded
        path has no constraint either — comparing a program against itself
        would be vacuous. Raises on mismatch."""
        if self.loss_scaler is not None and \
                bool(jax.device_get(jit_has_overflow(sharded_grads))):
            # fp16 overflow step: by design recoverable — the scaler's step
            # path skips it and shrinks the scale; inf/nan grads can never
            # match the fp32 reference, so checking would turn recovery
            # into a crash. WITHOUT a scaler there is no recovery path, so
            # non-finite grads fall through to the check and raise.
            return
        saved_constraint = self._grad_constraint
        saved_dtype = self.compute_dtype
        self._grad_constraint = None
        self.compute_dtype = jnp.float32
        # Force the plain (non-shard_map) program: under sequence
        # parallelism the reference must be the SERIAL function — building
        # the same SP decomposition twice would make the comparison
        # vacuous (an SP-specific psum/label-shift bug matches itself).
        self._force_serial_fwd_bwd = True
        try:
            ref_fn = self._get_fwd_bwd(len(inputs), static_kwargs,
                                       traced_kwargs.keys(), True)
            rep = mesh_lib.replicated(self.mesh)
            rep_params = jax.device_put(self.params, rep)
            rep_inputs = jax.device_put(inputs, rep)
            _, ref_grads = ref_fn(rep_params, rep_inputs, traced_kwargs,
                                  rng, scale)
        finally:
            self._grad_constraint = saved_constraint
            self.compute_dtype = saved_dtype
            self._force_serial_fwd_bwd = False
        tol = 2e-2 if saved_dtype != jnp.float32 else 1e-4
        if self.sequence_parallel_enabled() and \
                mesh_lib.sp_size(self.mesh) > 1:
            # SP is a genuinely different decomposition (ring-merge
            # softmax vs one-block attention): fp32 rounding scatter
            # reaches ~1e-3 elementwise while gradient NORMS agree to
            # ~0.1% — an sp-times scale bug still exceeds this by ~8x.
            tol = max(tol, 5e-3)
        for (path, a), b in zip(
                jax.tree_util.tree_flatten_with_path(sharded_grads)[0],
                jax.tree_util.tree_leaves(ref_grads)):
            a = np.asarray(jax.device_get(a), np.float32)
            b = np.asarray(jax.device_get(b), np.float32)
            if not np.allclose(a, b, rtol=tol, atol=tol):
                raise RuntimeError(
                    "pg_correctness_test: sharded gradient for {} diverges "
                    "from the fp32 replicated reference (max abs diff "
                    "{})".format(jax.tree_util.keystr(path),
                                 np.abs(a - b).max()))

    # --------------------------------------------------------------- backward

    def allreduce_gradients(self, bucket_size=MEMORY_OPT_ALLREDUCE_SIZE):
        """No-op on TPU: gradient reduction is a GSPMD sharding constraint
        inserted by XLA (reference engine.py:832-846 does explicit bucketed
        allreduce). Kept for API parity."""
        return None

    def csr_allreduce_no_retain(self, csr_list):
        """Average a list of CSRTensors across data-parallel workers
        (reference csr_allreduce_no_retain, engine.py:1186-1200).

        Single-controller GSPMD: the per-worker dense grads were already
        averaged inside the jitted program, so the host-visible CSR values
        are global — only the 1/N scaling semantics remain. Multi-controller
        shard_map pipelines use runtime.csr_tensor.csr_allreduce directly.
        """
        from deepspeed_tpu.runtime.csr_tensor import CSRTensor
        return [CSRTensor(indices=c.indices, values=c.values,
                          dense_size=c.dense_size) for c in csr_list]

    def sparse_allreduce_bucket(self, bucket):
        return self.csr_allreduce_no_retain(bucket)

    def backward(self, loss, allreduce_gradients=True, release_loss=False):
        """Accumulate the gradients computed in :meth:`forward`.

        The reference scales loss by 1/gas and runs autograd
        (engine.py:848-927); here the grads already exist (fused fwd+bwd), so
        backward just folds them into the accumulation buffer.
        """
        assert self._cached_grads is not None, \
            "backward() called without a prior forward()"
        self._last_loss = loss

        if self.wall_clock_breakdown():
            self.timers("backward_microstep").start()
            self.timers("backward").start()

        gas = self.gradient_accumulation_steps()
        grads = self._cached_grads
        self._cached_grads = None

        if isinstance(grads, _StreamedGrads):
            # Already staged on host during the fused backward; gas == 1
            # is enforced at forward, so there is nothing to fold.
            self._grad_acc = grads
            if self.wall_clock_breakdown():
                self.timers("backward").stop()
                self.timers("backward_microstep").stop()
            return loss

        with self.tracer.timed("train/backward", step_num=self.global_steps):
            if self._grad_acc is None:
                if gas > 1:
                    self._grad_acc = jax.tree_util.tree_map(
                        lambda g: g / gas, grads)
                else:
                    self._grad_acc = grads
            else:
                self._grad_acc = jax.tree_util.tree_map(
                    lambda a, g: a + g / gas, self._grad_acc, grads)

        if self.wall_clock_breakdown():
            self.timers("backward").stop(wait_for=self._grad_acc)
            self.timers("backward_microstep").stop()

        return loss

    # ------------------------------------------------------------------- step

    def is_gradient_accumulation_boundary(self):
        return (self.micro_steps + 1) % self.gradient_accumulation_steps() == 0

    def zero_grad(self):
        self._grad_acc = None
        self._cached_grads = None

    def get_lr(self):
        return [g["lr"] for g in self.optimizer.param_groups]

    def set_lr(self, lr):
        for g in self.optimizer.param_groups:
            g["lr"] = lr

    def get_mom(self):
        return [g.get("betas", (0.0, 0.0))[0] for g in self.optimizer.param_groups]

    def _get_update_fn(self):
        if self._update_fn is not None:
            return self._update_fn
        optimizer = self.optimizer
        clip = self.gradient_clipping()

        @jax.named_scope("optimizer")
        def update(params, opt_state, grads, inv_scale, lr, beta1, beta2):
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(jnp.float32) * inv_scale, grads)
            if clip > 0.0:
                grads, _ = clip_grad_norm_(grads, clip)
            return optimizer.update(params, grads, opt_state, lr=lr,
                                    betas=(beta1, beta2))

        out_shardings = None
        if self._shardings_ready:
            out_shardings = (self.param_sharding, self.opt_state_sharding)
        self._update_fn = jax.jit(update, out_shardings=out_shardings,
                                  donate_argnums=(0, 1))
        return self._update_fn

    def _take_model_step(self, lr_kwargs=None):
        grads = self._grad_acc
        self._grad_acc = None
        assert grads is not None, "step() called with no accumulated gradients"

        overflow = False
        cur_scale = 1.0
        if self.loss_scaler is not None:
            cur_scale = self.loss_scaler.loss_scale
            if isinstance(grads, _StreamedGrads):
                # inf/nan in any leaf propagates into its squared norm.
                overflow = not bool(np.isfinite(np.float64(
                    np.asarray(jax.device_get(grads.sqnorms),
                               np.float64).sum())))
            else:
                overflow = bool(jax.device_get(jit_has_overflow(grads)))
            self.loss_scaler.update_scale(overflow)

        if overflow:
            self.skipped_steps += 1
            if isinstance(grads, _StreamedGrads) and \
                    self._offload is not None:
                # The streamed backward DONATED the device param buffers;
                # a skipped step never reaches _offload_step's re-upload,
                # so restore params from the host master here or the next
                # forward would feed deleted arrays into jit.
                self._offload_restore_params()
            log_dist("OVERFLOW! Skipping step. Attempted loss scale: {}, "
                     "reducing to {}".format(cur_scale,
                                             self.loss_scaler.loss_scale),
                     ranks=[0])
        else:
            group = self.optimizer.param_groups[0]
            beta1, beta2 = group.get("betas", (0.9, 0.999))
            if self._offload_mode():
                self._offload_step(grads, 1.0 / cur_scale, group["lr"])
            else:
                update_fn = self._get_update_fn()
                self.params, self.opt_state = update_fn(
                    self.params, self.opt_state, grads,
                    jnp.float32(1.0 / cur_scale),
                    jnp.float32(group["lr"]),
                    jnp.float32(beta1), jnp.float32(beta2))

            if self.lr_scheduler is not None:
                self.lr_scheduler.step(**(lr_kwargs or {}))
            report_progress = self.global_rank == 0
            if report_progress and \
                    (self.global_steps + 1) % self.steps_per_print() == 0:
                self._report_progress(self.global_steps + 1)

        self.global_steps += 1
        self.global_samples += self.train_batch_size()
        self._tensorboard_step_events()
        if hasattr(self.optimizer, "notify_step"):
            # OnebitAdam freeze bookkeeping (reference onebit_adam.py:369-372).
            # Keyed off applied updates (the jitted state['step']), not
            # global_steps, so fp16 overflow-skipped steps don't desync the
            # host flag from the compiled phase switch.
            was_frozen = getattr(self.optimizer, "adam_freeze_key", None)
            self.optimizer.notify_step(self.global_steps - self.skipped_steps)
            if was_frozen is not None and \
                    was_frozen != self.optimizer.adam_freeze_key:
                # The phase flag is traced into the compiled update program
                # on the shard_map path; drop the cache so the frozen phase
                # re-traces (the cond path is phase-agnostic but re-jitting
                # once is harmless).
                self._update_fn = None

    # ------------------------------------------------------- ZeRO-Offload tier

    def _init_offload(self):
        """Build the host-resident fp32 master + optimizer state.

        The reference keeps fp32 master partitions + Adam moments in pinned
        CPU memory and updates them with the AVX cpu_adam op
        (stage2.py:156,326-342, cpu_adam.cpp). Here: one contiguous fp32
        buffer per role (master/m/v) on the host; opt_state exposes per-leaf
        numpy *views* into those buffers so checkpoint save/load works
        unchanged; the C++ op updates the whole flat buffer in one
        OpenMP pass (no per-tensor launches — the multi_tensor_apply idea,
        done by layout instead of kernel machinery).
        """
        leaves, treedef = jax.tree_util.tree_flatten(self.params)
        shapes = [l.shape for l in leaves]
        sizes = [int(np.prod(s)) if len(s) else 1 for s in shapes]
        offsets = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)
        total = int(offsets[-1])
        master = np.empty(total, np.float32)
        for leaf, off, size in zip(leaves, offsets[:-1], sizes):
            master[off:off + size] = np.asarray(
                jax.device_get(leaf), dtype=np.float32).ravel()
        m = np.zeros(total, np.float32)
        v = np.zeros(total, np.float32)

        def views(buf):
            return jax.tree_util.tree_unflatten(treedef, [
                buf[off:off + size].reshape(shape) for off, size, shape in
                zip(offsets[:-1], sizes, shapes)])

        self._offload = {
            "treedef": treedef, "shapes": shapes, "sizes": sizes,
            "offsets": offsets, "total": total,
            "master": master, "m": m, "v": v, "step": 0,
        }
        self.opt_state = {
            "step": np.int32(0),
            "exp_avg": views(m),
            "exp_avg_sq": views(v),
        }
        # The fp32 master now lives on host — device params drop to the
        # compute dtype (the reference keeps fp16 params on device + fp32
        # masters in pinned CPU memory, stage2.py:156,326-342). At 1.5B this
        # halves params+grads HBM from 12.4 GB to 6.2 GB.
        if self.compute_dtype != jnp.float32:
            cast = self._cast_to_compute
            self.params = cast(self.params)

    def _get_offload_pre_fn(self):
        """Jitted DEVICE-side unscale + global-norm clip, run BEFORE the
        host copy (the reference computes grad norms GPU-side pre-copy,
        stage2.py:818-840; doing it on host serialized the whole step)."""
        if self._offload_pre_fn is not None:
            return self._offload_pre_fn
        clip = self.gradient_clipping()

        def pre(grads, inv_scale):
            # Norms in f32, storage kept in the grad dtype, input buffers
            # donated: at 1.5B+ a full fp32 copy of the grads alongside the
            # bf16 originals would OOM a 16 GB chip.
            scale = inv_scale
            if clip > 0.0:
                norm = utils_global_norm(grads)
                scale = scale * jnp.minimum(
                    clip / (norm * inv_scale + 1e-6), 1.0)
            return jax.tree_util.tree_map(
                lambda x: (x.astype(jnp.float32) * scale).astype(x.dtype),
                grads)

        self._offload_pre_fn = jax.jit(pre, donate_argnums=0)
        return self._offload_pre_fn

    def _host_pack_lib(self):
        """The host flatten/unflatten op (csrc/utils, ≙ reference
        csrc/utils/flatten_unflatten.cpp used by engine/ZeRO bucketing):
        packs a chunk's grad leaves into the contiguous staging buffer
        with one OpenMP pass instead of a serial Python memcpy loop.
        Returns None when the op cannot build (numpy fallback)."""
        lib = getattr(self, "_host_pack_lib_cache", None)
        if lib is None and not getattr(self, "_host_pack_failed", False):
            try:
                from deepspeed_tpu.op_builder import UtilsBuilder
                lib = self._host_pack_lib_cache = UtilsBuilder().load()
            except Exception as e:
                self._host_pack_failed = True
                logger.info("utils op unavailable (%s); offload staging "
                            "uses the numpy pack loop", e)
        return lib

    def _offload_chunks(self):
        """Group flat-buffer leaf indices into ~16 MB transfer chunks for the
        copy/compute/copy pipeline."""
        target = 4 * 1024 * 1024  # fp32 elements (~16 MB)
        chunks, cur, cur_n = [], [], 0
        for i, size in enumerate(self._offload["sizes"]):
            cur.append(i)
            cur_n += size
            if cur_n >= target:
                chunks.append(cur)
                cur, cur_n = [], 0
        if cur:
            chunks.append(cur)
        return chunks

    def _offload_restore_params(self):
        """Re-materialize device params from the host fp32 master.

        Needed by the overflow-skip path under stream_gradients: the
        streamed backward donated the device param buffers, and a skipped
        step never reaches _offload_step's normal re-upload."""
        off = self._offload
        dtypes = [l.dtype for l in off["treedef"].flatten_up_to(self.params)]
        shard_leaves = off["treedef"].flatten_up_to(self.param_sharding) \
            if self._shardings_ready else [None] * len(off["sizes"])
        leaves = []
        for i in range(len(off["sizes"])):
            o, size = int(off["offsets"][i]), off["sizes"][i]
            host = off["master"][o:o + size].reshape(off["shapes"][i])
            arr = jnp.asarray(host, dtype=dtypes[i])
            if shard_leaves[i] is not None:
                arr = jax.device_put(arr, shard_leaves[i])
            leaves.append(arr)
        self.params = jax.tree_util.tree_unflatten(off["treedef"], leaves)

    def _offload_step(self, grads, inv_scale, lr):
        """Pipelined host optimizer step (reference's cpu-offload block,
        stage2.py:740-940 + DeepSpeedCPUAdam.step): grads are unscaled and
        clipped on device, streamed to host in chunks with
        copy_to_host_async, and the C++ OpenMP Adam runs on chunk i while
        chunk i+1 is still in flight and chunk i-1's updated params upload
        (async dispatch) — the double-buffering the reference builds with
        pinned memory + a migration stream (stage2.py:775-817)."""
        if self._offload is None:
            self._init_offload()
        off = self._offload
        opt = self.optimizer

        streamed = isinstance(grads, _StreamedGrads)
        if streamed:
            # Grads already live in off["stream_g"] (io_callback during
            # backward) — but only once the completion token resolves; the
            # callbacks are unordered and nothing else in the step depends
            # on them.
            jax.device_get(grads.token)
            # Unscale + global-norm clip become one host-side scale
            # factor, from the device-computed squared norms.
            clip = self.gradient_clipping()
            total_sq = float(np.asarray(jax.device_get(grads.sqnorms),
                                        np.float64).sum())
            host_scale = float(inv_scale)
            if clip > 0.0:
                norm = np.sqrt(total_sq) * float(inv_scale)
                host_scale *= min(clip / (norm + 1e-6), 1.0)
            g_leaves = None
        else:
            grads = self._get_offload_pre_fn()(grads, jnp.float32(inv_scale))
            g_leaves = off["treedef"].flatten_up_to(grads)
            del grads
            for leaf in g_leaves:
                if hasattr(leaf, "copy_to_host_async"):
                    leaf.copy_to_host_async()

        off["step"] += 1
        param_leaves = off["treedef"].flatten_up_to(self.params)
        dtypes = [l.dtype for l in param_leaves]
        shard_leaves = off["treedef"].flatten_up_to(self.param_sharding) \
            if self._shardings_ready else [None] * len(off["sizes"])
        new_leaves = [None] * len(param_leaves)
        # Release the old device params: the master (host) is authoritative,
        # and at 1.5B+ holding old params + grads + new params concurrently
        # would exceed a 16 GB chip. Leaves free as their refs drop. The
        # finally-block re-materializes params from the master even if a
        # chunk fails mid-loop — otherwise the next forward() would see
        # params=None and silently re-initialize fresh weights.
        self.params = None
        del param_leaves

        def upload(i):
            o, size = int(off["offsets"][i]), off["sizes"][i]
            host = off["master"][o:o + size].reshape(off["shapes"][i])
            arr = jnp.asarray(host, dtype=dtypes[i])
            if shard_leaves[i] is not None:
                arr = jax.device_put(arr, shard_leaves[i])
            return arr

        def stage(chunk):
            """Produce the chunk's contiguous fp32 grad view: streamed mode
            scales the already-host-resident span in place (overwritten
            next step); otherwise wait for the chunk's async device->host
            copies and pack them into one staging buffer."""
            t0 = time.time()
            lo = int(off["offsets"][chunk[0]])
            hi = int(off["offsets"][chunk[-1]] + off["sizes"][chunk[-1]])
            if streamed:
                host_g = off["stream_g"][lo:hi]
                if host_scale != 1.0:
                    np.multiply(host_g, host_scale, out=host_g)
                return host_g, lo, hi, time.time() - t0
            host_g = np.empty(hi - lo, np.float32)
            # D2H wait + fp32 cast per leaf first; the pack into the
            # contiguous staging buffer is then one OpenMP ds_flatten
            # call (chunk offsets are consecutive, so cumulative-size
            # packing lands each span at its flat-buffer offset).
            host_leaves = []
            for i in chunk:
                host_leaves.append(np.ascontiguousarray(np.asarray(
                    g_leaves[i], dtype=np.float32).ravel()))
                g_leaves[i] = None  # free this grad leaf's HBM now
            lib = self._host_pack_lib()
            if lib is not None:
                from deepspeed_tpu.op_builder import UtilsBuilder
                UtilsBuilder.flatten_into(lib, host_g, host_leaves)
            else:
                for t, i in zip(host_leaves, chunk):
                    o, size = int(off["offsets"][i]), off["sizes"][i]
                    host_g[o - lo:o - lo + size] = t
            return host_g, lo, hi, time.time() - t0

        # Double-buffered staging: a single worker thread stages chunk i+1
        # (copy-wait + memcpy pack, both GIL-releasing) while the C++ Adam
        # (ctypes call, GIL released) runs chunk i on the main thread.
        # Timing sums are kept per phase so the achieved overlap ratio
        # (serial sum / wall) is observable — the reference quantified its
        # fused copy the same way (ops/adam/cpu_adam.py:29-31).
        timing = {"stage_s": 0.0, "adam_s": 0.0, "upload_s": 0.0}
        t_wall = time.time()
        chunks = list(self._offload_chunks())
        pool = getattr(self, "_offload_pool", None)
        if pool is None:
            # One long-lived staging worker per engine — a per-step
            # executor would pay thread spawn/join every optimizer step.
            pool = self._offload_pool = ThreadPoolExecutor(max_workers=1)
        nxt = None
        try:
            nxt = pool.submit(stage, chunks[0]) if chunks else None
            for ci, chunk in enumerate(chunks):
                host_g, lo, hi, t_stage = nxt.result()
                timing["stage_s"] += t_stage
                nxt = pool.submit(stage, chunks[ci + 1]) \
                    if ci + 1 < len(chunks) else None
                step_kwargs = {"step": off["step"], "lr": lr}
                if getattr(opt, "supports_segments", False):
                    # LAMB trust ratios are per-tensor: each leaf in the
                    # chunk is its own span.
                    step_kwargs["segments"] = [
                        (int(off["offsets"][i]) - lo, off["sizes"][i])
                        for i in chunk]
                t0 = time.time()
                opt.step_flat(off["master"][lo:hi], host_g,
                              off["m"][lo:hi], off["v"][lo:hi],
                              **step_kwargs)
                timing["adam_s"] += time.time() - t0
                # Upload this chunk's updated params; device_put dispatches
                # asynchronously, overlapping the next chunk's host Adam.
                t0 = time.time()
                for i in chunk:
                    new_leaves[i] = upload(i)
                timing["upload_s"] += time.time() - t0
        finally:
            if nxt is not None:
                # Drain the in-flight staging future (it mutates g_leaves)
                # before tearing down state on an exception path.
                try:
                    nxt.result()
                except Exception:
                    pass
            del g_leaves
            self.params = jax.tree_util.tree_unflatten(
                off["treedef"],
                [leaf if leaf is not None else upload(i)
                 for i, leaf in enumerate(new_leaves)])
        timing["wall_s"] = time.time() - t_wall
        timing["chunks"] = len(chunks)
        timing["overlap_ratio"] = (
            (timing["stage_s"] + timing["adam_s"] + timing["upload_s"])
            / max(timing["wall_s"], 1e-9))
        self._offload_timing = timing
        self.opt_state["step"] = np.int32(off["step"])

    def step(self, lr_kwargs=None):
        """Weight update at gradient-accumulation boundaries
        (reference engine.py:989-1074)."""
        if self.wall_clock_breakdown():
            self.timers("step_microstep").start()
            self.timers("step").start()

        assert self.optimizer is not None, \
            "must provide optimizer during init in order to use step"

        if self.is_gradient_accumulation_boundary():
            if self.progressive_layer_drop:
                self.progressive_layer_drop.update_state(self.global_steps)
            with self.tracer.timed("train/update",
                                   step_num=self.global_steps):
                self._take_model_step(lr_kwargs)
            self._mark_ready()

        self.tput_timer.stop(self.global_rank == 0)

        if self.wall_clock_breakdown():
            self.timers("step").stop(wait_for=self.params)
            self.timers("step_microstep").stop()
            if self.is_gradient_accumulation_boundary() and \
                    self.global_steps % self.steps_per_print() == 0:
                self.timers.log([
                    "forward_microstep", "backward_microstep", "step_microstep"
                ], memory_breakdown=self.memory_breakdown())

        self.micro_steps += 1

    def _report_progress(self, step):
        """The ``steps_per_print`` line, fed from the telemetry registry:
        the same gauges Prometheus/TensorBoard export, so the printed
        step log and the scraped metrics can never disagree."""
        lr = self.get_lr() if self.optimizer else [0.0]
        mom = self.get_mom() if self.optimizer else [0.0]
        snap = self.telemetry.snapshot()
        log_dist(
            "step={}, skipped={}, lr={}, mom={}, samples={}, "
            "samples/sec={:.2f}".format(
                step, self.skipped_steps, lr, mom,
                int(snap.get("global_samples", 0)),
                snap.get("samples_per_sec", 0.0)), ranks=[0])

    # --------------------------------------------------------- fused fast path

    def _onebit_spmd_eligible(self):
        """True when train_batch should run the 1-bit Adam shard_map hot
        path: per-worker LOCAL gradients feed local momentum, and the
        compression-phase exchange is the genuinely compressed two-phase
        collective (uint8 n/8 + scales on the wire) instead of the dense
        GSPMD gradient average (reference: compression replaces the dense
        allreduce entirely, onebit_adam.py:369-372 + README '5x less
        communication'). Requires a pure-DP mesh: the reference's 1-bit
        Adam is likewise DP-only (no ZeRO composition)."""
        from deepspeed_tpu.runtime.fp16.onebit_adam import OnebitAdam
        return (isinstance(self.optimizer, OnebitAdam)
                and mesh_lib.dp_size(self.mesh) > 1
                and mesh_lib.mp_size(self.mesh) <= 1
                and mesh_lib.pp_size(self.mesh) <= 1
                and mesh_lib.sp_size(self.mesh) <= 1
                and not self.zero_optimization()
                and not self.sparse_gradients_enabled())

    def _build_onebit_spmd_fused(self, frozen):
        """Fused fwd+bwd+1-bit-Adam step under shard_map over 'data'.

        Unlike the GSPMD fused path (XLA inserts a dense f32 gradient
        all-reduce), gradients here stay LOCAL to each worker: the warmup
        phase pmeans them explicitly (dense Adam semantics), and the
        frozen phase feeds them straight into local momentum, exchanging
        ONLY sign-packed momentum via compressed_allreduce — the wire
        payload is uint8 n/8 + one fp32 scale per phase. ``frozen`` is
        static (a collective cannot live inside lax.cond), so the step
        re-traces once at the freeze boundary; train_batch keys its cache
        on the phase."""
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from deepspeed_tpu.runtime.fp16.onebit_adam import onebit_adam_update

        mesh = self.mesh
        axis = mesh_lib.DATA_AXIS
        dp = mesh_lib.dp_size(mesh)
        module = self.module
        cast = self._cast_to_compute
        clip = self.gradient_clipping()
        if frozen:
            self._warn_onebit_clip_once(clip)
        opt = self.optimizer
        group = opt.param_groups[0]
        eps = group["eps"]
        weight_decay = group["weight_decay"]
        freeze_step = opt.freeze_step
        tm = jax.tree_util.tree_map

        rep_spec = lambda tree: tm(lambda _: P(), tree)
        row_spec = lambda tree: tm(lambda _: P(axis), tree)
        state_spec = {
            "step": P(),
            "exp_avg": rep_spec(self.opt_state["exp_avg"]),
            "exp_avg_sq": rep_spec(self.opt_state["exp_avg_sq"]),
            "worker_error": row_spec(self.opt_state["worker_error"]),
            "server_error": row_spec(self.opt_state["server_error"]),
        }
        def spmd(params, opt_state, largs, rng, lr, beta1, beta2):
            def loss_fn(p):
                cp = cast(p)
                return module.apply({"params": cp}, *largs,
                                    rngs={"dropout": rng})

            loss, grads = jax.value_and_grad(loss_fn)(params)
            loss = jax.lax.pmean(loss, axis)
            grads = tm(lambda g: g.astype(jnp.float32), grads)
            if not frozen:
                # Warmup = dense Adam: average gradients explicitly (the
                # allreduce GSPMD would have inserted), then clip.
                grads = tm(lambda g: jax.lax.pmean(g, axis), grads)
                if clip > 0.0:
                    grads, _ = clip_grad_norm_(grads, clip)
            # Frozen phase: NO gradient averaging and no grad clipping —
            # local grads feed local momentum, the quantization scale
            # bounds the exchanged update (reference compression phase,
            # onebit_adam.py:319-355, operates unclipped on local grads).
            st = dict(opt_state)
            st["worker_error"] = tm(lambda e: e[0],
                                    opt_state["worker_error"])
            st["server_error"] = tm(lambda e: e[0],
                                    opt_state["server_error"])
            new_params, new_st = onebit_adam_update(
                params, grads, st, lr=lr, beta1=beta1, beta2=beta2,
                eps=eps, weight_decay=weight_decay,
                freeze_step=freeze_step, axis_name=axis, world_size=dp,
                frozen=frozen)
            new_st["worker_error"] = tm(lambda e: e[None],
                                        new_st["worker_error"])
            new_st["server_error"] = tm(lambda e: e[None],
                                        new_st["server_error"])
            return loss, new_params, new_st

        def train_step(params, opt_state, args, rng, lr, beta1, beta2):
            in_specs = (rep_spec(params), state_spec,
                        tuple(mesh_lib.batch_partition_spec(x, dp)
                              for x in args), P(), P(), P(), P())
            out_specs = (P(), rep_spec(params), state_spec)
            return shard_map(spmd, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)(
                params, opt_state, args, rng, lr, beta1, beta2)

        out_shardings = None
        if self._shardings_ready:
            out_shardings = (None, self.param_sharding,
                             self.opt_state_sharding)
        return jax.jit(train_step, donate_argnums=(0, 1),
                       out_shardings=out_shardings)

    def _dp_region_specs(self, batch):
        """The PartitionSpecs of every parameter leaf and of every gradient
        leaf (``zero_shardings``' own, read off ``param_sharding`` and
        ``grad_sharding``) where the fused step's forward and backward run
        PER CHIP, else None. They do when all that is split is the batch:
        the mesh splits only 'data', a chip computes with the whole weights
        (ZeRO 0-2) and every leaf of ``batch`` splits over 'data'. Any other
        mesh, stage 3, a batch leaf that stays whole and one chip keep the
        program GSPMD partitions."""
        dp = mesh_lib.dp_size(self.mesh)
        stage = self.zero_optimization_stage() \
            if self.zero_optimization() else 0
        rows = mesh_lib.P(mesh_lib.DATA_AXIS)
        leaves = jax.tree_util.tree_leaves(batch)
        if dp <= 1 or self.mesh.size != dp or stage > 2 or not leaves or \
                any(mesh_lib.batch_partition_spec(x, dp) != rows
                    for x in leaves):
            return None
        return tuple(jax.tree_util.tree_map(lambda sh: sh.spec, tree)
                     for tree in (self.param_sharding, self.grad_sharding))

    def _dp_value_and_grad(self, loss_fn, specs, params, args, rng):
        """``(loss, grads)`` of ``loss_fn(params, args, rng)`` under data
        parallelism, with ZeRO's collectives WRITTEN: one ``shard_map``
        over 'data' in which each chip holds the whole (cast) ``params``
        and its own rows of ``args``, so the optimizer's partition cannot
        reach into the model (GSPMD propagated the tied table's
        feature-split gradient into the LM head: a contraction-sharded
        head over every chip's rows and an all-reduce of the float32
        logits a chunk). ``specs`` is ``_dp_region_specs``' pair. A
        parameter leaf whose spec names 'data' (ZeRO 1-2: ``params`` is the
        sharded master's cast) enters as the chip's shard and is made whole
        by ``all_gather`` on that dim at the region's head, a leaf a
        collective in the tree's order so that a later block's gather can
        run under an earlier block's compute (the reference's sharded
        allgather of updated fp16 params, stage2.py:1444-1477). The loss
        leaves as the mean over chips (equal rows a chip: the global mean,
        and what the reference's ranks compute); a gradient leaf whose spec
        names 'data' leaves through ``psum_scatter`` onto that dim (ZeRO-2,
        reference stage2.py:675-738), any other through ``psum``, in the
        dtype of ``params``: differentiate with respect to the whole CAST
        parameters and the cotangents cross the wire in the compute dtype."""
        axis = mesh_lib.DATA_AXIS
        dp = mesh_lib.dp_size(self.mesh)
        is_spec = lambda x: isinstance(x, mesh_lib.P)
        param_specs, grad_specs = specs
        gather_dims, dims = (
            [list(spec).index(axis) if axis in spec else None
             for spec in jax.tree_util.tree_leaves(tree, is_leaf=is_spec)]
            for tree in specs)
        self._zero_leaves = (sum(d is not None for d in dims),
                             sum(d is None for d in dims),
                             sum(d is not None for d in gather_dims))

        def spmd(shards, largs, rng):
            with jax.named_scope("zero_gather"):
                leaves, treedef = jax.tree_util.tree_flatten(shards)
                params = jax.tree_util.tree_unflatten(treedef, [
                    p if d is None else
                    jax.lax.all_gather(p, axis, axis=d, tiled=True)
                    for p, d in zip(leaves, gather_dims)])
            rng = jax.random.fold_in(rng, jax.lax.axis_index(axis))
            # 1/dp of the chip's mean loss: the cotangents are then the
            # global mean's from the start, and the sums below finish it.
            loss, grads = jax.value_and_grad(
                lambda p: loss_fn(p, largs, rng) / dp)(params)
            with jax.named_scope("zero_reduce"):
                leaves, treedef = jax.tree_util.tree_flatten(grads)
                leaves = [
                    jax.lax.psum(g, axis) if d is None else
                    jax.lax.psum_scatter(g, axis, scatter_dimension=d,
                                         tiled=True)
                    for g, d in zip(leaves, dims)]
                return jax.lax.psum(loss, axis), \
                    jax.tree_util.tree_unflatten(treedef, leaves)

        whole = mesh_lib.P()
        rows = jax.tree_util.tree_map(
            lambda _: mesh_lib.P(axis), args)
        return jax.shard_map(
            spmd, mesh=self.mesh, in_specs=(param_specs, rows, whole),
            out_specs=(whole, grad_specs), check_vma=False)(
            params, args, rng)

    def _build_fused_step(self):
        """The fused fwd+bwd+update program of ``train_batch``."""
        module = self.module
        cast = self._cast_to_compute
        clip = self.gradient_clipping()
        optimizer = self.optimizer
        grad_constraint = self._grad_constraint
        mesh = self.mesh

        # Named for what it is: a trace's hlo_module reads
        # jit_train_step. Its regions (jax.named_scope): zero_gather (the
        # sharded master's cast made whole, where the step writes it), the
        # model's (embed, block/ln|attn|mlp, lm_head: models/gpt2.py),
        # zero_reduce (the gradients' collectives, where they are
        # written) and optimizer (gradient cast, clip, update).
        def train_step(params, opt_state, args, rng, lr, beta1, beta2):
            def loss_fn(cp, args, rng):
                with kernels_on_mesh(mesh):
                    return module.apply({"params": cp}, *args,
                                        rngs={"dropout": rng})

            # Decided by what this trace can see: mesh, stage, shapes.
            specs = self._dp_region_specs(args)
            if specs is None:
                self._zero_leaves = (0, 0, 0)
                loss, grads = jax.value_and_grad(
                    lambda p: loss_fn(cast(p), args, rng))(params)
                if grad_constraint is not None:
                    grads = jax.lax.with_sharding_constraint(
                        grads, grad_constraint)
            else:
                loss, grads = self._dp_value_and_grad(
                    loss_fn, specs, cast(params, gather=False), args, rng)
            with jax.named_scope("optimizer"):
                grads = jax.tree_util.tree_map(
                    lambda g: g.astype(jnp.float32), grads)
                if clip > 0.0:
                    grads, _ = clip_grad_norm_(grads, clip)
                new_params, new_state = optimizer.update(
                    params, grads, opt_state, lr=lr,
                    betas=(beta1, beta2))
            return loss, new_params, new_state

        out_shardings = None
        if self._shardings_ready:
            out_shardings = (None, self.param_sharding,
                             self.opt_state_sharding)
        return jax.jit(train_step, donate_argnums=(0, 1),
                       out_shardings=out_shardings)

    def train_batch(self, batch=None, data_iter=None):
        """Fused fwd+bwd+update in ONE jitted XLA program (donated buffers).

        The perf path for gas==1, non-fp16 configs — XLA overlaps gradient
        collectives with backward compute the way the reference's
        overlap_comm/IPG machinery does by hand (stage2.py:283-287).
        Falls back to forward/backward/step when fp16 overflow bookkeeping or
        gradient accumulation requires host control.
        """
        if batch is None:
            assert data_iter is not None
            batch = next(data_iter)
        # A StepTraceAnnotation-style span (``_r``): the profiler's tools
        # group the trace by it.
        with self.tracer.timed("train/step", step_num=self.global_steps,
                               _r=1):
            return self._train_batch(batch)

    def _train_batch(self, batch):
        self._first_step_begins()
        if self.fp16_enabled() or self.gradient_accumulation_steps() > 1 or \
                self._offload_mode():
            loss = self.forward(*batch) if isinstance(batch, (tuple, list)) \
                else self.forward(batch)
            self.backward(loss)
            self.step()
            return loss

        if isinstance(batch, (tuple, list)):
            inputs = tuple(jnp.asarray(x) if isinstance(x, np.ndarray) else x
                           for x in batch)
        else:
            inputs = (jnp.asarray(batch),)
        with self.tracer.timed("train/shard_batch"):
            inputs = mesh_lib.shard_batch(self.mesh, inputs)

        if self.params is None:
            self._lazy_init(inputs, {})

        if self._onebit_spmd_eligible():
            # The 1-bit hot path keys on the phase: the compressed
            # collective cannot live under lax.cond, so freeze re-traces.
            key = ("onebit", len(inputs),
                   bool(self.optimizer.adam_freeze_key))
            if key not in self._fused_step_cache:
                self._fused_step_cache[key] = self._build_onebit_spmd_fused(
                    frozen=key[2])
        else:
            key = len(inputs)
        first = key not in self._fused_step_cache
        if first:
            with process_recorder().timed("setup/programs"):
                self._fused_step_cache[key] = self._build_fused_step()

        self.tput_timer.start()
        group = self.optimizer.param_groups[0]
        beta1, beta2 = group.get("betas", (0.9, 0.999))
        jitted = self._fused_step_cache[key]
        rng = self._next_rng()
        lr_d = jnp.float32(group["lr"])
        b1_d, b2_d = jnp.float32(beta1), jnp.float32(beta2)
        # Shapes-only xray capture of the exact fused program about to
        # run (params/opt_state are donated — the stash abstracts
        # leaves immediately and retains no buffer).
        self.xray.stash("fused_train_step[{}]".format(key), jitted,
                        self.params, self.opt_state, inputs, rng,
                        lr_d, b1_d, b2_d,
                        donate=("params", "opt_state"))
        def dispatch():
            return jitted(self.params, self.opt_state, inputs, rng, lr_d,
                          b1_d, b2_d)

        with self.tracer.timed("train/dispatch"):
            loss, self.params, self.opt_state = \
                _traced_with_room(dispatch) if first else dispatch()
        # After the dispatch: the first one traces the step, and the trace
        # is what counts the leaves.
        self.xray.note("fused_train_step[{}]".format(key),
                       tokens=self.train_batch_size(),
                       zero_scatter_leaves=self._zero_leaves[0],
                       zero_psum_leaves=self._zero_leaves[1],
                       zero_gather_leaves=self._zero_leaves[2],
                       zero_master_shard_share=self._master_shard_share)
        with self.tracer.timed("train/bookkeeping"):
            if self.lr_scheduler is not None:
                self.lr_scheduler.step()
            self.global_steps += 1
            self.global_samples += self.train_batch_size()
            self.micro_steps += 1
            self._last_loss = loss
            self._tensorboard_step_events()
            if hasattr(self.optimizer, "notify_step"):
                self.optimizer.notify_step(
                    self.global_steps - self.skipped_steps)
            self.tput_timer.stop(True)
        self._mark_ready()
        return loss

    # -------------------------------------------------------- flops profiler

    def flops_profiler_enabled(self):
        return self._config.flops_profiler_config.enabled

    def flops_profiler_start_step(self):
        return self._config.flops_profiler_config.start_step

    def flops_profiler_end_step(self):
        return self._config.flops_profiler_config.end_step

    def _start_flops_profiler(self):
        from deepspeed_tpu.profiling.flops_profiler.profiler import FlopsProfiler
        # Share this engine's observatory: profiled programs and the
        # fused-step stash land in ONE record set (and one AOT-analysis
        # cache), so perf_xray() and the profiler report agree.
        self.flops_profiler = FlopsProfiler(self.module, xray=self.xray)
        self.flops_profiler.start_profile()

    def _stop_flops_profiler(self):
        if hasattr(self, "flops_profiler"):
            self.flops_profiler.stop_profile()
            self.flops_profiler.print_model_profile(
                top_modules=self._config.flops_profiler_config.top_modules)
            self.flops_profiler.end_profile()

    def perf_xray(self):
        """The schema-versioned ``perf_xray`` section for the training
        side: every fused step program this engine compiled, with HLO
        fingerprint, cost-model flops/bytes, and the peak-HBM split.
        First call pays the one-time AOT analysis (off the step path)."""
        return self.xray.to_json()

    # ------------------------------------------------------------- checkpoint

    def _get_ckpt_name(self, checkpoints_path, tag):
        mp_rank = 0 if self.mpu is None else self.mpu.get_model_parallel_rank()
        return os.path.join(checkpoints_path, str(tag),
                            "mp_rank_{:02d}_model_states.pt".format(mp_rank))

    def _get_zero_ckpt_name(self, checkpoints_path, tag, dp_rank=0):
        mp_rank = 0 if self.mpu is None else self.mpu.get_model_parallel_rank()
        zero_ckpt_name = os.path.join(
            checkpoints_path, str(tag),
            "zero_pp_rank_{}_mp_rank_{:02d}optim_states.pt".format(
                dp_rank, mp_rank))
        return zero_ckpt_name

    def _to_host(self, tree):
        return jax.tree_util.tree_map(lambda x: np.asarray(jax.device_get(x)),
                                      tree)

    def save_checkpoint(self, save_dir, tag=None, client_state=None,
                        save_latest=True):
        """Save the checkpoint set (reference engine.py:1461-1561): model
        states per mp-rank, zero optim states per (dp,mp) rank, 'latest' tag
        file. Serialization is numpy+pickle instead of torch.save."""
        if tag is None:
            tag = "global_step{}".format(self.global_steps)
        self._checkpoint_tag_validation(tag)

        save_path = self._get_ckpt_name(save_dir, tag)
        ensure_directory_exists(save_path)

        state = {
            "module": self._to_host(self.params),
            "optimizer": None if self.zero_optimization() else
            self._optimizer_state_for_save(),
            "lr_scheduler": self.lr_scheduler.state_dict()
            if self.lr_scheduler is not None else None,
            "csr_tensor_module_names": [],
            "skipped_steps": self.skipped_steps,
            "global_steps": self.global_steps,
            "global_samples": self.global_samples,
            "dp_world_size": self.dp_world_size,
            "mp_world_size": self.mp_world_size,
            "loss_scaler": self.loss_scaler.__dict__.copy()
            if self.loss_scaler is not None else None,
        }
        if client_state is not None:
            state.update(client_state)
        with open(save_path, "wb") as f:
            pickle.dump(state, f)
        logger.info("Saving model checkpoint: {}".format(save_path))

        if self.zero_optimization():
            self._save_zero_checkpoint(save_dir, tag)

        if save_latest:
            with open(os.path.join(save_dir, "latest"), "w") as fd:
                fd.write(tag)
        return True

    def _save_zero_checkpoint(self, save_dir, tag):
        """Write the zero optim-state files. With elastic_checkpoint (the
        default, reference zero/config.py:25), state is split into one
        world-size-agnostic shard file per dp rank (reference
        stage1.py:848-1078's elastic format): a later load at a DIFFERENT dp
        world size reassembles the full logical state from however many shard
        files exist and re-partitions onto the current mesh."""
        opt_sd = self._optimizer_state_for_save()
        elastic = self.zero_elastic_checkpoint() and not self._offload_mode()
        dp_world = mesh_lib.dp_size(self.mesh)
        if not elastic or dp_world <= 1:
            zero_path = self._get_zero_ckpt_name(save_dir, tag)
            ensure_directory_exists(zero_path)
            with open(zero_path, "wb") as f:
                pickle.dump({"optimizer_state_dict": opt_sd}, f)
            return
        state_host = opt_sd.pop("state")
        for r in range(dp_world):
            zero_path = self._get_zero_ckpt_name(save_dir, tag, dp_rank=r)
            ensure_directory_exists(zero_path)
            with open(zero_path, "wb") as f:
                pickle.dump({
                    "optimizer_state_dict": opt_sd,
                    "state_shards": self._partition_state_for_rank(
                        state_host, r, dp_world),
                    "zero_dp_world_size": dp_world,
                }, f)

    def _partition_state_for_rank(self, state_host, dp_rank, dp_world):
        """Shard one dp rank's slice of host optimizer state. Each leaf
        becomes ('shard', dim, slice) along its data-sharded dim, or
        ('full', array) in rank 0's file only (replicated/indivisible
        leaves — e.g. the scalar step, small biases)."""
        def slice_leaf(leaf):
            arr = np.asarray(leaf)
            spec = mesh_lib._leaf_spec_over_axis(arr, mesh_lib.DATA_AXIS,
                                                 dp_world)
            dim = next((i for i, ax in enumerate(spec)
                        if ax == mesh_lib.DATA_AXIS), None)
            if dim is None:
                return ("full", arr) if dp_rank == 0 else ("ref",)
            per = arr.shape[dim] // dp_world
            idx = [slice(None)] * arr.ndim
            idx[dim] = slice(dp_rank * per, (dp_rank + 1) * per)
            return ("shard", dim, arr[tuple(idx)])

        return jax.tree_util.tree_map(slice_leaf, state_host)

    @staticmethod
    def _merge_state_shards(shard_trees):
        """Inverse of _partition_state_for_rank: reassemble the full logical
        state from every saved dp rank's shard tree."""
        def merge(*entries):
            first = entries[0]
            if first[0] == "full" or first[0] == "ref":
                full = next(e for e in entries if e[0] == "full")
                return full[1]
            dim = first[1]
            return np.concatenate([e[2] for e in entries], axis=dim)

        return jax.tree_util.tree_map(
            merge, *shard_trees,
            is_leaf=lambda x: isinstance(x, tuple) and len(x) > 0 and
            x[0] in ("full", "ref", "shard"))

    def _optimizer_state_for_save(self):
        sd = {"state": self._to_host(self.opt_state)
              if self.opt_state is not None else None}
        if self._offload_mode() and self._offload is not None:
            # Persist the host fp32 master weights: resume must keep full
            # master precision (reference saves
            # single_partition_of_fp32_groups, stage2.py:1704); rebuilding
            # from bf16 params would drift the training trajectory.
            sd["fp32_master"] = self._offload["master"].copy()
        if hasattr(self.optimizer, "state_dict"):
            sd.update(self.optimizer.state_dict())
        return sd

    def _load_zero_state(self, load_dir, tag):
        """Read zero optim-state file(s). Elastic layout: every saved dp
        rank's shard file is read and the full logical state reassembled, so
        loading at a different dp world size than the save re-partitions
        naturally (reference engine.py:1376-1442 + stage1.py:946-1023)."""
        zero_path = self._get_zero_ckpt_name(load_dir, tag, dp_rank=0)
        if not os.path.exists(zero_path):
            return None
        with open(zero_path, "rb") as f:
            head = pickle.load(f)
        if "state_shards" not in head:
            return head["optimizer_state_dict"]  # non-elastic single file
        saved_world = head["zero_dp_world_size"]
        shard_trees = [head["state_shards"]]
        for r in range(1, saved_world):
            path_r = self._get_zero_ckpt_name(load_dir, tag, dp_rank=r)
            assert os.path.exists(path_r), (
                "elastic zero checkpoint saved at dp={} is missing shard "
                "file {}".format(saved_world, path_r))
            with open(path_r, "rb") as f:
                shard_trees.append(pickle.load(f)["state_shards"])
        opt_sd = dict(head["optimizer_state_dict"])
        opt_sd["state"] = self._merge_state_shards(shard_trees)
        if saved_world != mesh_lib.dp_size(self.mesh):
            log_dist("elastic zero checkpoint: re-partitioning optimizer "
                     "state saved at dp={} onto dp={}".format(
                         saved_world, mesh_lib.dp_size(self.mesh)), ranks=[0])
        return opt_sd

    def _checkpoint_tag_validation(self, tag):
        """Cross-rank tag consistency (reference engine.py:1444-1459): every
        process sha1-hashes the tag, hashes are all-gathered over processes,
        and a mismatch warns or fails per checkpoint_tag_validation_fail. In
        a single-process (single-controller) run the gather is trivial."""
        if not self.checkpoint_tag_validation_enabled():
            return
        tag_hash = hashlib.sha1(str(tag).encode()).hexdigest()
        local = np.frombuffer(bytes.fromhex(tag_hash), np.uint8)
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils
            gathered = np.asarray(
                multihost_utils.process_allgather(local))
            valid = bool((gathered == gathered[0]).all())
        else:
            valid = True
        if not valid:
            msg = "checkpoint tag '{}' inconsistent across ranks: not all " \
                  "processes computed the same tag hash".format(tag)
            if self.checkpoint_tag_validation_fail():
                raise RuntimeError(msg)
            logger.warning(msg)
        return tag_hash

    def load_checkpoint(self,
                        load_dir,
                        tag=None,
                        load_module_strict=True,
                        load_optimizer_states=True,
                        load_lr_scheduler_states=True):
        """Load checkpoint (reference engine.py:1271-1374). Returns
        (load_path, client_state)."""
        if tag is None:
            latest_path = os.path.join(load_dir, "latest")
            if os.path.isfile(latest_path):
                with open(latest_path, "r") as fd:
                    tag = fd.read().strip()
            else:
                logger.warning(
                    "Unable to find latest file at {}, if trying to load "
                    "latest checkpoint please pass an explicit tag".format(
                        latest_path))
                return None, None

        load_path = self._get_ckpt_name(load_dir, tag)
        if not os.path.exists(load_path):
            logger.warning(
                "Client provided checkpoint load path: {} does not exist ... "
                "attempting to load from zero shards".format(load_path))
            return None, None

        with open(load_path, "rb") as f:
            checkpoint = pickle.load(f)

        self.params = jax.tree_util.tree_map(jnp.asarray, checkpoint["module"])
        if self.optimizer is not None and self.opt_state is None and \
                not self._offload_mode():
            self.opt_state = self.optimizer.init_state(self.params)
        self._setup_shardings()
        if self._offload_mode():
            self._init_offload()

        if load_optimizer_states:
            opt_sd = None
            if self.zero_optimization():
                opt_sd = self._load_zero_state(load_dir, tag)
            else:
                opt_sd = checkpoint.get("optimizer")
            if opt_sd is not None and opt_sd.get("state") is not None:
                if self._offload_mode():
                    # Copy saved moments into the host buffers (views).
                    saved = opt_sd["state"]
                    off = self._offload
                    for buf, key in ((off["m"], "exp_avg"),
                                     (off["v"], "exp_avg_sq")):
                        leaves = off["treedef"].flatten_up_to(saved[key])
                        for leaf, o, size in zip(leaves, off["offsets"][:-1],
                                                 off["sizes"]):
                            buf[o:o + size] = np.asarray(leaf,
                                                         np.float32).ravel()
                    if opt_sd.get("fp32_master") is not None:
                        # Full-precision master resume (reference
                        # load_from_fp32_weights, stage2.py:1718-1741): the
                        # saved fp32 buffer is authoritative, not the bf16
                        # module params _init_offload rebuilt it from.
                        off["master"][:] = opt_sd["fp32_master"]
                    off["step"] = int(saved["step"])
                    self.opt_state["step"] = np.int32(off["step"])
                else:
                    self.opt_state = jax.tree_util.tree_map(
                        jnp.asarray, opt_sd["state"])
                    self.opt_state = jax.device_put(self.opt_state,
                                                    self.opt_state_sharding)
                if hasattr(self.optimizer, "load_state_dict"):
                    self.optimizer.load_state_dict(opt_sd)

        if load_lr_scheduler_states and self.lr_scheduler is not None and \
                checkpoint.get("lr_scheduler") is not None:
            self.lr_scheduler.load_state_dict(checkpoint["lr_scheduler"])

        if self.loss_scaler is not None and checkpoint.get("loss_scaler"):
            self.loss_scaler.__dict__.update(checkpoint["loss_scaler"])

        self.global_steps = checkpoint.get("global_steps", 0)
        self.global_samples = checkpoint.get(
            "global_samples", self.global_steps * self.train_batch_size())
        self.skipped_steps = checkpoint.get("skipped_steps", 0)
        self.micro_steps = self.global_steps * self.gradient_accumulation_steps()
        if hasattr(self.optimizer, "notify_step"):
            # Resync host-side freeze bookkeeping with the restored
            # counters: a resume past freeze_step must select the frozen
            # (compressed) program for its FIRST step, not run one
            # warmup-phase step until notify_step flips the flag post-step.
            self.optimizer.notify_step(self.global_steps - self.skipped_steps)

        deepspeed_states = [
            "module", "optimizer", "lr_scheduler", "csr_tensor_module_names",
            "skipped_steps", "global_steps", "global_samples",
            "dp_world_size", "mp_world_size", "loss_scaler",
        ]
        client_state = {k: v for k, v in checkpoint.items()
                        if k not in deepspeed_states}
        return load_path, client_state

    # -------------------------------------------------------------- misc state

    def _dump_state(self):
        self._config.print("DeepSpeedEngine configuration")

    @property
    def ds_config(self):
        return self._config
