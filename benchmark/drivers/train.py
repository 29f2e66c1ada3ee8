"""Driver of the ``train`` kind: a training loop over ``deepspeed.initialize``
and the fused ``train_batch``, as a user writes it.

Set-up: weights from the seed in one jitted call (on the device, or on the
host where the configuration's deployment says the model exceeds one chip),
the engine, the reference's loss on the first batch, then ``warmup_steps``
steps on that one batch. Window: steps on distinct seeded batches, dispatched
back to back with at most ``in_flight`` unfinished, until ``--seconds`` have
passed; every step's loss is waited for in turn, and the rate is the median
over chunks of ``rate_chunk_steps`` steps (``harness.median_chunk_rate``).
"""

import collections
import contextlib
import time

import numpy as np

from benchmark import traffic
from benchmark.harness import median_chunk_rate, note, span

# bf16 compute against the float32 reference on the same weights and batch:
# a loss near ln(50257) = 10.8 is a mean over thousands of tokens, so the
# rounding of single logits (one bf16 ulp at 10.8 is 0.06) averages out: on
# the chip the two differ by 4e-5 at 355M (PERF.md, PR 22). The tolerance
# leaves a hundred times that for other seeds and sizes, and is still far
# under what a lower precision costs (bf16 accumulation in the head, or an
# fp8 matmul, moves a loss of 11 in the second decimal).
LOSS_TOL = 0.005
# Distinct batches drawn up front; a window of more steps than this cycles.
BATCH_POOL = 256


def moments_sharded(engine, ways):
    """How the optimizer moments lie on the mesh: (leaves sharded ``ways``
    ways, leaves not, and the largest of those not)."""
    import jax

    sharded, whole, largest_whole = 0, 0, 0
    for name in ("exp_avg", "exp_avg_sq"):
        for leaf in jax.tree.leaves(engine.opt_state[name]):
            shard = leaf.addressable_shards[0].data
            if len(leaf.sharding.device_set) == ways and \
                    shard.size * ways == leaf.size:
                sharded += 1
            else:
                whole += 1
                largest_whole = max(largest_whole, int(leaf.size))
    return sharded, whole, largest_whole


def _steps(engine, pool, first, stop, in_flight, done_t, losses):
    """Dispatch steps from batch ``first`` on, at least one, until
    ``stop(dispatched)`` says so, keeping at most ``in_flight`` unfinished;
    then wait for the rest. Returns the number of steps."""
    import jax

    def finish_one():
        with span("bench/train_wait"):
            loss = pending.popleft()
            jax.block_until_ready(loss)
        done_t.append(time.perf_counter())
        losses.append(loss)

    pending = collections.deque()
    n = 0
    while n == 0 or not stop(n):
        ids = pool[(first + n) % len(pool)]
        with span("bench/train_dispatch"):
            pending.append(engine.train_batch(batch=(ids, ids)))
        n += 1
        if len(pending) > in_flight:
            finish_one()
    while pending:
        finish_one()
    return n


def run(run):
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu as deepspeed
    from deepspeed_tpu.parallel.mesh import build_mesh

    config, mix = run.cell.config, run.cell.traffic
    deployment = config["deployment"]
    train = deployment["train"]
    chips = run.cell.chips
    batch = int(mix["batch_per_chip"]) * chips
    seq_len = int(mix["seq_len"])
    model = run.model
    flops_per_token = model.train_flops_per_token(seq_len)
    note(event="sizes", global_batch=batch, seq_len=seq_len, chips=chips,
         zero_stage=train["zero_stage"], host_init=train["host_init"],
         flops_per_token=flops_per_token, **model.sizes())

    pool = traffic.token_batches(run.seed, BATCH_POOL, batch, seq_len,
                                 model.vocab_size, mix)
    # deepspeed.initialize makes both moments whole on JAX's default device
    # before it shards them: where the deployment says so the host is that
    # device, as a user whose model exceeds one chip has to do today.
    host = bool(train["host_init"])
    params = model.init_params(run.seed, on_host=host)
    jax.block_until_ready(params)
    note(event="setup", done="weights")
    mesh = None if chips == len(jax.devices()) else \
        build_mesh(devices=run.devices)
    with jax.default_device(jax.devices("cpu")[0]) if host \
            else contextlib.nullcontext():
        engine, _, _, _ = deepspeed.initialize(
            model=model.module, model_parameters=params, mesh=mesh,
            config_params={
                "train_batch_size": batch,
                "optimizer": mix["optimizer"],
                "bf16": {"enabled":
                         deployment["compute_dtype"] == "bfloat16"},
                "zero_optimization": {"stage": int(train["zero_stage"])},
            })
    del params  # the engine owns (and donates) them from here on
    note(event="setup", done="engine")

    ref_loss = float(model.reference_loss(engine.params,
                                          jnp.asarray(pool[0])))
    note(event="setup", done="reference")
    warm = []
    for _ in range(int(mix["warmup_steps"])):
        warm.append(float(engine.train_batch(batch=(pool[0], pool[0]))))
        note(event="setup", done="warmup step", loss=warm[-1])
    note(event="warmup", reference_first_loss=ref_loss, warmup_losses=warm,
         loss_after_warmup=warm[-1], loss_tolerance=LOSS_TOL)

    in_flight = int(mix["in_flight"])
    done_t, losses = [], []
    run.window_opens()
    t0 = time.perf_counter()
    steps = _steps(engine, pool, 1,
                   lambda n: time.perf_counter() - t0 >= run.seconds,
                   in_flight, done_t, losses)
    t1 = done_t[-1]
    run.window_closes()
    window_losses = np.asarray(jax.device_get(losses), np.float64)

    trace_steps = 0
    if run.trace:
        trace_steps = int(mix["trace_steps"])
        with run.traced():
            _steps(engine, pool, 1 + steps, lambda n: n >= trace_steps,
                   in_flight, [], [])

    checks = {
        "first_loss_matches_reference": abs(warm[0] - ref_loss) <= LOSS_TOL,
        "first_loss_minus_reference": warm[0] - ref_loss,
        "warmup_loss_falls": warm[-1] < warm[0],
        "window_losses_finite": bool(np.all(np.isfinite(window_losses))),
    }
    if chips > 1 and int(train["zero_stage"]) >= 1:
        sharded, whole, largest = moments_sharded(engine, chips)
        # Replicated state would be a different result, not a faster one:
        # only leaves too small to matter, or not divisible, may stay whole.
        checks["moments_sharded"] = sharded > 0 and largest < 4096 * chips
        checks["moment_leaves_sharded"] = sharded
        checks["moment_leaves_whole"] = whole
    correct = all(v for v in checks.values() if isinstance(v, bool))

    gaps = np.diff(done_t[in_flight:])
    step_ms = float(np.median(gaps) * 1e3) if len(gaps) else None
    # The first `in_flight` steps fill the queue; rates start after them.
    rate, chunks = median_chunk_rate(
        done_t[in_flight:], [batch * seq_len] * (steps - in_flight),
        int(mix["rate_chunk_steps"]))
    tok_s_chip = rate / chips
    note(event="window", steps=steps, window_s=t1 - t0, step_ms=step_ms,
         rate_chunks=chunks,
         whole_window_tok_s_chip=steps * batch * seq_len / (t1 - t0) / chips,
         longest_gap_ms=float(np.max(gaps) * 1e3) if len(gaps) else None,
         first_window_loss=float(window_losses[0]),
         last_window_loss=float(window_losses[-1]),
         train_tok_s_chip=tok_s_chip)
    if run.trace:
        # The compiler's own account of the step, after the window: this
        # lowers the step again, which a --trace 0 run does not pay for.
        for row in engine.perf_xray()["programs"]:
            if not row["superseded"]:
                note(event="program", **{k: row.get(k) for k in (
                    "program", "kernel_calls", "collectives",
                    "argument_bytes", "output_bytes", "temp_bytes",
                    "alias_bytes", "peak_hbm_bytes", "error")})
    return {
        "correct": correct, "checks": checks,
        "attempted": steps,
        "failed": int(np.sum(~np.isfinite(window_losses))),
        "values": {"train_tok_s_chip": tok_s_chip,
                   "train_step_ms": step_ms},
        "counters": {"steps": steps, "trace_steps": trace_steps,
                     "global_batch": batch, "seq_len": seq_len,
                     "chips": chips, "n_layer": model.n_layer,
                     "n_head": model.n_head, "head_dim": model.head_dim,
                     "flops_per_token": flops_per_token},
    }
