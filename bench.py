"""Benchmark harness — prints ONE JSON line for the driver.

Metric: GPT-2 training MFU on the available TPU chip(s), via the engine's
fused train_batch path (bf16, ZeRO-0 single chip). vs_baseline compares our
model-flops utilization against the reference's published 52%-of-peak
BERT-large number (BASELINE.md: 66 TFLOPS on a 125 TFLOP V100,
docs/_posts/2020-05-19-bert-record.md:14).

The measurement runs in the one process this script was started in (the
chip belongs to one process at a time) and needs a TPU: without one it exits
non-zero and prints no metric, unless ``JAX_PLATFORMS=cpu`` asked for the
tiny CPU smoke explicitly. Every line names its device under
``extra.device``.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np


# The reference anchor is DeepSpeed's published BERT-large record, 66 TFLOPS
# on a 125-TFLOP V100 = 52% of peak (BASELINE.md, reference
# docs/_posts/2020-05-19-bert-record.md:14). The chip's own peak comes from
# the one table keyed by device kind (telemetry/xray.py DEVICE_PEAKS).
REF_MFU = 0.52


def _peak_flops():
    """bf16 peak FLOP/s of the attached chip; a device kind without a
    peaks row raises (no chip is given another chip's row)."""
    import jax

    from deepspeed_tpu.telemetry.xray import device_peaks

    return device_peaks(jax.devices()[0].device_kind)["flops_per_s"]


def _git_state():
    """Short commit hash of the measured code, '-dirty'-suffixed when the
    working tree differs — stamped into every bench artifact so a number
    can be dated against the code it measured. None outside a git
    checkout."""
    cwd = os.path.dirname(os.path.abspath(__file__))
    try:
        r = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                           capture_output=True, text=True, cwd=cwd,
                           timeout=10)
        if r.returncode != 0:
            return None
        head = r.stdout.strip()
        d = subprocess.run(["git", "status", "--porcelain", "-uno"],
                           capture_output=True, text=True, cwd=cwd,
                           timeout=10)
        if d.returncode == 0 and d.stdout.strip():
            head += "-dirty"
        return head
    except (OSError, subprocess.TimeoutExpired):
        return None


def _require_tpu_or_exit():
    """A measurement needs the chip: without a TPU the run exits non-zero
    and prints no metric. ``JAX_PLATFORMS=cpu``, asked for explicitly, runs
    the tiny smoke sizes instead, under their own metric names."""
    import jax

    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        return
    if jax.default_backend() != "tpu":
        print("bench: needs a TPU, got backend {!r} (set JAX_PLATFORMS=cpu "
              "to ask for the tiny CPU smoke)".format(jax.default_backend()),
              file=sys.stderr)
        sys.exit(3)


_ANALYSIS_SUMMARY = None


def _analysis_summary():
    """graftlint stamp for bench artifacts: {counts_by_rule, new,
    baseline_size}. One AST pass over the package per process (cached)."""
    global _ANALYSIS_SUMMARY
    if _ANALYSIS_SUMMARY is None:
        import deepspeed_tpu
        from deepspeed_tpu import analysis
        pkg = os.path.dirname(os.path.abspath(deepspeed_tpu.__file__))
        baseline_path = os.path.join(pkg, "analysis", "baseline.json")
        findings = analysis.collect_findings([pkg])
        baseline = (analysis.load_baseline(baseline_path)
                    if os.path.exists(baseline_path) else [])
        new, _stale = analysis.apply_baseline(findings, baseline)
        counts = {}
        for f in findings:
            counts[f.rule] = counts.get(f.rule, 0) + 1
        _ANALYSIS_SUMMARY = {
            "counts_by_rule": counts,
            "new": len(new),
            "baseline_size": len(baseline),
        }
    return _ANALYSIS_SUMMARY


_TRACE_SUMMARY = None


def _note_trace(target, alerts_fired=None):
    """Fold ``target``'s trace/alert state into the next artifact.

    ``target`` is anything with ``trace_recorders()`` (engine, fleet,
    FrontDoor); span counts, ring drops, and fired alert names (from
    ``target.alerts`` when present, or the explicit ``alerts_fired``
    list) are stamped into ``extra.trace_summary`` by ``_emit`` so
    every perf artifact records what the observability plane saw while
    the number was earned."""
    global _TRACE_SUMMARY
    spans = {}
    dropped = 0
    for site, rec in target.trace_recorders().items():
        counts = rec.span_counts()
        if counts:
            spans[site] = sum(counts.values())
        dropped += int(getattr(rec, "dropped", 0))
    if alerts_fired is None:
        alerts = getattr(target, "alerts", None)
        alerts_fired = ([r["rule"] for r in alerts.fired()]
                        if alerts is not None else [])
    _TRACE_SUMMARY = {
        "spans": spans,
        "spans_dropped": dropped,
        "alerts_fired": list(alerts_fired),
    }


def _emit(result):
    """Print one driver-facing JSON line. Every line names the device it
    was measured on — platform, device kind and count, as JAX reports
    them — so a number can never be read apart from its chip."""
    import jax

    devices = jax.devices()
    result["extra"]["device"] = {"platform": devices[0].platform,
                                 "kind": devices[0].device_kind,
                                 "count": len(devices)}
    result["extra"].setdefault("git_hash", _git_state())
    # Static health travels with every perf artifact: graftlint finding
    # counts by rule + baseline size (docs/ANALYSIS.md), so the perf
    # trajectory records whether the tree was contract-clean when the
    # number was earned.
    result["extra"].setdefault("analysis_findings", _analysis_summary())
    # Which ModelAdapter produced this artifact. Serving measurements
    # set it from engine.metrics(); everything else measures the GPT-2
    # source directly, which the GPT-2 adapter wraps unchanged.
    result["extra"].setdefault("adapter", "gpt2")
    # Observability plane state for this measurement (PR 14): span counts
    # per recorder site, ring drops, and any SLO alerts that fired.
    if _TRACE_SUMMARY is not None:
        result["extra"].setdefault("trace_summary", dict(_TRACE_SUMMARY))
    print(json.dumps(result), flush=True)


def _timed_chunks(step_fn, batches, chunk, tokens_per_step, label):
    """Run ``step_fn`` over ``batches`` in chunks, each chunk timed to a
    barrier and logged to stderr as it lands.

    Returns (chunk_log, last_loss): one dict per chunk — rate
    (tok/s/chip), steps, dt_s, and the backend that executed it. The
    headline rate is max of the rates, the device-limited number.

    step_fn(batch) must return the step's loss (device scalar); float()
    on it waits for the step and everything queued before it."""
    import jax

    platform = jax.default_backend()
    chunk_log = []
    loss_val = None
    i = 0
    while i < len(batches):
        ids_chunk = batches[i:i + chunk]
        t0 = time.time()
        for b in ids_chunk:
            loss = step_fn(b)
        loss_val = float(loss)
        dt = time.time() - t0
        rate = tokens_per_step * len(ids_chunk) / dt
        chunk_log.append({"rate": round(rate, 1),
                          "steps": len(ids_chunk),
                          "dt_s": round(dt, 4),
                          "platform": platform})
        print("bench: {} chunk {} steps in {:.3f}s -> {:.0f} "
              "tok/s/chip [{}]".format(label, len(ids_chunk), dt, rate,
                                       platform),
              file=sys.stderr, flush=True)
        i += chunk
    return chunk_log, loss_val


def flops_per_token(cfg, seq):
    """Training FLOPs per token: 6*N for the dense matmuls plus the causal
    attention score/value matmuls — per layer 2 matmuls x 2 FLOPs x T x C
    = 4TC fwd, halved by causality to 2TC, x3 for fwd+bwd = 6TC."""
    n_params = cfg.num_params()
    attn = 6 * cfg.n_layer * seq * cfg.n_embd
    return 6 * n_params + attn


def main_xl():
    """North-star capacity mode (`bench.py --xl`): GPT-2 1.5B with ZeRO-2 +
    cpu_offload + remat on ONE chip — the reference's ZeRO-Offload headline
    is model CAPACITY on a single device (13B on a 32 GB V100,
    docs/_posts/2020-09-09-ZeRO-Offload.md:10; a 16 GB v5e fits ~6-7B by the
    same bf16-params+host-master arithmetic, and 1.5B is the measured
    config). Off by default: one step moves ~9 GB over the host link."""
    import jax

    _require_tpu_or_exit()

    import deepspeed_tpu as deepspeed
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        cfg = GPT2Config.gpt2_xl(dropout=0.0, remat=True)
        batch, seq = 2, 1024
    else:
        # Explicit CPU request: 1.5B on host compute takes hours —
        # exercise the same offload path at smoke size.
        cfg = GPT2Config.tiny(dropout=0.0)
        batch, seq = 2, 64
    model = GPT2LMHeadModel(cfg)
    engine, _, _, _ = deepspeed.initialize(
        model=model,
        config_params={
            "train_batch_size": batch,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": 2, "cpu_offload": True},
        })
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, size=(batch, seq))
    loss = engine(ids, ids)
    engine.backward(loss)
    engine.step()  # compile + first host step
    times = []
    for _ in range(2):
        t0 = time.time()
        loss = engine(ids, ids)
        engine.backward(loss)
        engine.step()
        times.append(time.time() - t0)
    tok = batch * seq / min(times)
    _emit({
        "metric": ("gpt2_1.5b_offload_tokens_per_sec_per_chip" if on_tpu
                   else "gpt2_tiny_offload_smoke_tokens_per_sec"),
        "value": round(tok, 2),
        "unit": "tokens/s/chip",
        # capacity parity: 1.5B trains on one chip (1.0 only when the
        # real config actually ran)
        "vs_baseline": 1.0 if on_tpu else 0.0,
        "extra": {
            "params": cfg.num_params(),
            "loss": float(loss),
            "step_seconds": round(min(times), 1),
            # The overlap claim must be measured, not asserted — phase
            # sums vs wall from the engine's own timeline (overlap_ratio
            # > 1 means phases overlapped).
            "offload_timing": engine.offload_timing(),
            **({"mfu": round(tok * flops_per_token(cfg, seq)
                             / _peak_flops(), 4),
                "platform": "tpu"}
               if on_tpu else {}),
        },
    })


def main_xl_compute():
    """North-star COMPUTE mode (`bench.py --xl-compute`): GPT-2 1.5B
    fwd+bwd MFU on ONE chip, separated from the offload transfer.

    `--xl` measures the full offload step, ~9 GB/step over the host
    link — it answers the capacity question, not the compute one. This
    mode answers
    the other half (BASELINE.md's >=45%-MFU-at-1.5B north star needs a
    pod; this is the single-chip compute anchor for it): bf16 params
    (3.1 GB) + remat activations fit in 16 GB HBM without optimizer
    state, so the fused fwd+bwd program runs at full 1.5B scale on the
    chip. MFU counts the same 6N+attention model flops as the 355M
    headline — remat recompute is NOT counted as useful work, so the
    number is directly comparable."""
    import jax
    import jax.numpy as jnp

    _require_tpu_or_exit()

    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        cfg = GPT2Config.gpt2_xl(dropout=0.0, remat=True)
        batch, seq, steps, peak_flops = 4, 1024, 8, _peak_flops()
    else:
        cfg = GPT2Config.tiny(dropout=0.0, remat=True)
        batch, seq, steps, peak_flops = 2, 64, 3, 1e12

    model = GPT2LMHeadModel(cfg)
    rng = np.random.RandomState(0)
    ids0 = jnp.asarray(rng.randint(0, cfg.vocab_size, size=(batch, seq)))
    params = jax.jit(lambda: model.init(
        jax.random.PRNGKey(0), ids0, labels=ids0)["params"])()
    # fp32 init -> bf16 working copy; donate the fp32 tree so the chip
    # never holds both (1.5B fp32 alone is 6.2 GB).
    params = jax.jit(
        lambda p: jax.tree.map(lambda x: x.astype(jnp.bfloat16), p),
        donate_argnums=0)(params)

    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, ids: model.apply({"params": p}, ids, labels=ids)))

    batches = [jnp.asarray(rng.randint(0, cfg.vocab_size,
                                       size=(batch, seq)))
               for _ in range(steps + 1)]
    loss, _ = grad_fn(params, batches[0])
    float(loss)  # compile + warm

    chunk_log, loss = _timed_chunks(
        lambda ids: grad_fn(params, ids)[0], batches[1:],
        chunk=4, tokens_per_step=batch * seq, label="xl-compute")
    chunk_rates = [c["rate"] for c in chunk_log]
    tok = max(chunk_rates)
    mfu = tok * flops_per_token(cfg, seq) / peak_flops
    _emit({
        "metric": "gpt2_{}_compute_tokens_per_sec_per_chip".format(
            "1.5b" if on_tpu else "tiny"),
        "value": round(tok, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / REF_MFU, 4),
        "extra": {
            "mfu": round(mfu, 4),
            "platform": jax.default_backend(),
            "batch": batch,
            "seq": seq,
            "loss": loss,
            "params": cfg.num_params(),
            "chunk_rates": chunk_rates,
            "chunk_log": chunk_log,
            "note": "fwd+bwd only (no optimizer state on device): the "
                    "1.5B compute anchor; --xl carries the capacity/"
                    "offload story",
        },
    })


def _measure_gpt2(batch, seq, steps):
    """One timed GPT-2 355M training run (tiny model off-TPU); returns the
    result dict (not yet emitted)."""
    import jax

    import deepspeed_tpu as deepspeed
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel

    platform = jax.default_backend()
    # Size the model to the hardware: full GPT-2 355M on a real TPU chip,
    # tiny on CPU (so the harness still runs end-to-end anywhere).
    on_tpu = platform == "tpu"
    if on_tpu:
        # Measured-best single-chip config (v5e): Pallas flash attention
        # (2.1x over dense XLA at T=1024 fwd+bwd); chunked-XE loss keeps
        # logits out of HBM so batch 8 fits without remat.
        # n_positions follows the measured sequence: gpt2_medium's default
        # (1024) would assert on the sweep's T=2048/4096 rows.
        cfg = GPT2Config.gpt2_medium(dropout=0.0, use_flash_attention=True,
                                     n_positions=max(1024, seq))
        peak_flops = _peak_flops()
    else:
        cfg = GPT2Config.tiny(dropout=0.0)
        batch, seq, steps = 8, 64, 5
        peak_flops = 1e12

    model = GPT2LMHeadModel(cfg)
    # DS_BENCH_FP16=1 prices the fp16 path (dynamic loss scaling + the
    # kernels' unfused `dp - delta` form) at the headline shape; default
    # is the bf16 headline.
    fp16 = os.environ.get("DS_BENCH_FP16", "0") not in ("0", "", "false")
    precision_cfg = (
        {"fp16": {"enabled": True, "initial_scale_power": 16}}
        if fp16 else {"bf16": {"enabled": True}})
    engine, _, _, _ = deepspeed.initialize(
        model=model,
        config_params=dict({
            "train_batch_size": batch * jax.device_count(),
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "zero_optimization": {"stage": 2} if jax.device_count() > 1 else {},
        }, **precision_cfg))

    rng = np.random.RandomState(0)
    # Distinct batch per step, like a real input pipeline.
    batches = [
        rng.randint(0, cfg.vocab_size, size=(batch * jax.device_count(), seq))
        for _ in range(steps + 1)
    ]

    # Warmup/compile; the scalar fetch waits for the step.
    loss = engine.train_batch(batch=(batches[0], batches[0]))
    float(loss)

    chunk_log, loss = _timed_chunks(
        lambda ids: engine.train_batch(batch=(ids, ids)), batches[1:],
        chunk=5, tokens_per_step=batch * seq, label="headline")
    chunk_rates = [c["rate"] for c in chunk_log]
    tokens_per_sec_per_chip = max(chunk_rates)
    mfu = tokens_per_sec_per_chip * flops_per_token(cfg, seq) / peak_flops

    return {
        "metric": "gpt2_{}_tokens_per_sec_per_chip{}".format(
            "355m" if on_tpu else "tiny", "_fp16" if fp16 else ""),
        "value": round(tokens_per_sec_per_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / REF_MFU, 4),
        "extra": {
            "mfu": round(mfu, 4),
            "platform": platform,
            "devices": jax.device_count(),
            "batch": batch,
            "seq": seq,
            "precision": "fp16" if fp16 else "bf16",
            "loss": loss,
            "params": cfg.num_params(),
            "chunk_rates": chunk_rates,
            "chunk_log": chunk_log,
        },
    }


def _measure_bert(sparse, steps):
    """BERT-large MLM+NSP training throughput — the reference's own record
    config family (BASELINE.md: 66 TFLOPS/V100 = 52% of peak on BERT-large;
    docs/_posts/2020-05-19-bert-record.md:14). Dense mode runs the fused
    layer (flash attention) at T=512; sparse mode runs the plain encoder
    with the block-sparse Pallas kernel at T=4096 (the reference's sparse
    attention is its long-sequence story, README.md:17)."""
    import jax

    import deepspeed_tpu as deepspeed
    from deepspeed_tpu.models.bert import BertConfig, BertForPreTraining

    platform = jax.default_backend()
    on_tpu = platform == "tpu"
    if on_tpu:
        if sparse:
            from deepspeed_tpu.ops.sparse_attention import (
                FixedSparsityConfig)
            seq, batch = 4096, 2
            cfg = BertConfig.bert_large(
                max_position_embeddings=seq, use_fused_layer=False,
                sparse_attention_config=FixedSparsityConfig(
                    num_heads=16, block=64, attention="bidirectional"),
                hidden_dropout_prob=0.0,
                attention_probs_dropout_prob=0.0)
        else:
            seq, batch = 512, 16
            cfg = BertConfig.bert_large(hidden_dropout_prob=0.0,
                                        attention_probs_dropout_prob=0.0)
        peak_flops = _peak_flops()
    else:
        seq, batch = 128, 4
        kw = {}
        if sparse:
            from deepspeed_tpu.ops.sparse_attention import (
                FixedSparsityConfig)
            kw = dict(use_fused_layer=False,
                      sparse_attention_config=FixedSparsityConfig(
                          num_heads=4, block=32,
                          attention="bidirectional"))
        cfg = BertConfig.tiny(max_position_embeddings=seq,
                              hidden_dropout_prob=0.0,
                              attention_probs_dropout_prob=0.0, **kw)
        peak_flops = 1e12
    if cfg.sparse_attention_config is not None:
        layout = np.asarray(cfg.sparse_attention_config.make_layout(seq))
        density = float(layout.sum()) / layout.size
    else:
        density = 1.0

    engine, _, _, _ = deepspeed.initialize(
        model=BertForPreTraining(cfg),
        config_params={
            "train_batch_size": batch * jax.device_count(),
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "bf16": {"enabled": True},
        })

    rng = np.random.RandomState(0)

    def make_batch():
        ids = rng.randint(0, cfg.vocab_size, size=(batch, seq))
        labels = np.where(rng.rand(batch, seq) < 0.15, ids, -1)
        nsp = rng.randint(0, 2, size=(batch,))
        return (ids, np.ones_like(ids), np.zeros_like(ids), labels, nsp)

    batches = [make_batch() for _ in range(steps + 1)]
    loss = engine.train_batch(batch=batches[0])
    float(loss)  # compile barrier

    chunk_log, loss = _timed_chunks(
        lambda b: engine.train_batch(batch=b), batches[1:],
        chunk=4, tokens_per_step=batch * seq, label="bert")
    chunk_rates = [c["rate"] for c in chunk_log]
    tok = max(chunk_rates)

    n_params = int(sum(int(np.prod(l.shape)) for l in
                       jax.tree_util.tree_leaves(engine.params)))
    # 6*N dense matmul FLOPs/token + non-causal attention score/value
    # matmuls (4TC per layer fwd, x3 fwd+bwd = 12TC), density-scaled for
    # the block-sparse layout.
    attn = 12 * cfg.num_hidden_layers * seq * cfg.hidden_size * density
    mfu = tok * (6 * n_params + attn) / peak_flops

    _emit({
        "metric": "bert_{}{}_tokens_per_sec_per_chip".format(
            "large" if on_tpu else "tiny", "_sparse" if sparse else ""),
        "value": round(tok, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / REF_MFU, 4),
        "extra": {
            "mfu": round(mfu, 4),
            "platform": platform,
            "batch": batch,
            "seq": seq,
            "params": n_params,
            "loss": loss,
            "attention_density": round(density, 4),
            "chunk_rates": chunk_rates,
            "chunk_log": chunk_log,
        },
    })


def _decode_attention_probe(engine, reps=10, s=1):
    """Jitted micro-timing of ONE layer's decode-attention op at the
    engine's decode shape (worst-case frontier: every block active), on
    whichever path the engine engaged — flash kernel or dense einsum. The
    serving metric can't isolate the attention op from the rest of the
    decode step; this number makes the kernel A/B attributable in the
    bench artifact. ``s`` is the query width per step — 1 for plain
    decode, spec_k+1 when the speculative verify lane is the step shape.
    Returns (ms_per_call, engaged_flash)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.transformer.kernels import decode_attention as da

    g = engine._gcfg
    b = engine.config.max_slots
    h, d = g.n_head, g.n_embd // g.n_head
    rng = np.random.RandomState(0)
    if "block_tbl" in engine._pool:
        # Paged pool: probe the block-table kernel over a synthetic
        # arena with every row's pages mapped (worst-case frontier),
        # page 0 reserved as the trash page like the real arena.
        page_len = int(engine._pool["k"].shape[3])
        n_lp = int(engine._pool["block_tbl"].shape[1])
        t = page_len * n_lp
        q = jnp.asarray(rng.randn(b, h, s, d), g.dtype)
        k = jnp.asarray(rng.randn(b * n_lp + 1, h, page_len, d), g.dtype)
        v = jnp.asarray(rng.randn(b * n_lp + 1, h, page_len, d), g.dtype)
        tbl = jnp.asarray(
            np.arange(1, b * n_lp + 1, dtype=np.int32).reshape(b, n_lp))
        pos = jnp.full((b,), t - s, jnp.int32)
        use_flash = bool(g.use_flash_decode) and da.decode_supported(page_len)
        fn = da.flash_decode_attention_paged if use_flash \
            else da.decode_attention_paged_reference
        args = (q, k, v, tbl, pos)
    else:
        t = engine._pool["k"].shape[3]
        q = jnp.asarray(rng.randn(b, h, s, d), g.dtype)
        k = jnp.asarray(rng.randn(b, h, t, d), g.dtype)
        v = jnp.asarray(rng.randn(b, h, t, d), g.dtype)
        pos = jnp.full((b,), t - s, jnp.int32)
        use_flash = bool(g.use_flash_decode) and da.decode_supported(t)
        fn = da.flash_decode_attention if use_flash \
            else da.decode_attention_reference
        args = (q, k, v, pos)
    jitted = jax.jit(fn)
    jax.block_until_ready(jitted(*args))   # compile + warmup
    t0 = time.time()
    out = None
    for _ in range(reps):
        out = jitted(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / reps * 1e3, use_flash


def _measure_serving(smoke=False, flash_decode=None, spec_decode=True,
                     int8_kv=True, prefix_cache=True, host_offload=True,
                     paged_kv=True):
    """Continuous-batching serving benchmark (deepspeed_tpu/inference/).

    A synthetic Poisson request stream plays against the slotted engine:
    requests arrive at exponential inter-arrival times, admit into free
    slots at chunk boundaries, and decode concurrently. Reports tok/s,
    p50/p99 per-token decode latency, time-to-first-token and queue wait,
    and slot occupancy; ``vs_baseline`` is the throughput ratio against
    serving the SAME requests one at a time through
    models.generation.generate — the continuous-batching win itself.
    ``smoke`` runs the tiny model with a short stream (the tier-1
    in-process mode). ``flash_decode`` forces the decode-attention path
    (None: the engine's default — the Pallas kernel on TPU);
    ``--no-flash-decode`` sets False for the einsum side of the kernel
    A/B. ``spec_decode``
    enables n-gram speculative decoding (``--no-spec-decode`` for the
    A/B); the stamped ``accepted_per_step_*`` / ``draft_accept_rate``
    metrics attribute any throughput delta to draft acceptance. The
    prompts are REPETITION-HEAVY (each tiles its own short phrase) — the
    workload where prompt-lookup drafting has matches to find; the
    non-spec A/B serves the identical stream. ``int8_kv`` /
    ``prefix_cache`` / ``host_offload`` enable the KV memory hierarchy
    (docs/INFERENCE.md); the ``--no-int8-kv`` / ``--no-prefix-cache`` /
    ``--no-host-offload`` A/Bs suffix the metric name so hierarchy-on
    and hierarchy-off series never mix. ``paged_kv`` serves through the
    page-granular KV pool (``--no-paged-kv`` for the dense-pool A/B,
    suffixed ``_nopagedkv``)."""
    import jax

    import deepspeed_tpu as deepspeed
    from deepspeed_tpu.models.generation import generate
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    from deepspeed_tpu.ops.transformer.kernels import decode_attention as da

    platform = jax.default_backend()
    on_tpu = platform == "tpu" and not smoke
    if on_tpu:
        cfg = GPT2Config.gpt2_medium(dropout=0.0, use_flash_attention=True)
        n_req, rate = 48, 16.0           # requests, arrivals/sec
        serve_cfg = {"max_slots": 16, "max_len": 1024, "chunk_size": 16,
                     "max_queue": n_req}
        prompt_lens, max_new = (64, 256), 96
    else:
        # Tiny smoke stream: a fast arrival rate so the run is bounded by
        # decode, not by simulated arrival gaps.
        cfg = GPT2Config.tiny(dropout=0.0, use_flash_attention=False)
        n_req, rate = 10, 500.0
        serve_cfg = {"max_slots": 4, "max_len": 64, "chunk_size": 4,
                     "max_queue": n_req}
        prompt_lens, max_new = (4, 12), 8
    if flash_decode is not None:
        serve_cfg["use_flash_decode"] = flash_decode
    spec_on = bool(spec_decode)
    serve_cfg["spec_decode"] = spec_on
    int8_on = bool(int8_kv)
    prefix_on = bool(prefix_cache)
    offload_on = bool(host_offload)
    serve_cfg["int8_kv"] = int8_on
    serve_cfg["prefix_cache"] = prefix_on
    serve_cfg["host_offload"] = offload_on
    paged_on = bool(paged_kv)
    serve_cfg["paged_kv"] = paged_on
    if paged_on and not on_tpu:
        # Smoke page quantum: small pages on the tiny plane so the
        # arena holds more than one page per slot (the default 128
        # would swallow the whole 64-position smoke plane).
        serve_cfg["kv_page_len"] = 16
    if prefix_on and not on_tpu:
        # Tiny-plane smoke sizing: prefixes shorter than the 64-token
        # default so the prefix plane stays a sliver of the smoke pool.
        serve_cfg.update(prefix_slots=4, prefix_len=16, min_prefix_len=4)

    model = GPT2LMHeadModel(cfg)
    rng = np.random.RandomState(0)
    init_ids = rng.randint(0, cfg.vocab_size, size=(2, 16))
    import jax.numpy as jnp
    params = model.init(jax.random.PRNGKey(0),
                        jnp.asarray(init_ids))["params"]
    engine = deepspeed.init_inference(
        model=model, params=params, config={"inference": serve_cfg})

    # The stream: lengths from a SMALL set (each distinct length is one
    # sequential-baseline compile; the engine takes any length).
    # Repetition-heavy content: each request tiles its OWN random phrase
    # to length — natural text repeats itself, uniform-random tokens
    # never do, and the n-gram drafter needs self-matches to draft from.
    # Identical stream on the spec and non-spec sides of the A/B.
    lens = [int(prompt_lens[i % len(prompt_lens)]) for i in range(n_req)]
    prompts = [np.tile(rng.randint(0, cfg.vocab_size, size=(8,)),
                       -(-n // 8))[:n].astype(np.int32) for n in lens]
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n_req))

    # Warmup: the engine compiles its ONE mixed-step program on the
    # first request and the recompile detector warms itself after that
    # step; metrics(reset=True) opens a fresh window, so the measured
    # phase's counters, timers and latency percentiles carry NO warmup
    # pollution.
    from deepspeed_tpu.telemetry import PROFILE_DIR_ENV, profile_window

    engine.generate([prompts[lens.index(n)] for n in sorted(set(lens))],
                    max_new_tokens=2)
    engine.metrics(reset=True)

    t0 = time.time()
    submitted, reqs, done = 0, [], []
    peak_pages, page_util = 0, None
    with profile_window("serving"):
        while len(done) < n_req:
            now = time.time() - t0
            while submitted < n_req and arrivals[submitted] <= now:
                reqs.append(engine.submit(prompts[submitted],
                                          max_new_tokens=max_new))
                submitted += 1
            if engine.idle:
                time.sleep(max(arrivals[submitted] - (time.time() - t0),
                               0.0))
                continue
            done.extend(engine.step())
            if paged_on:
                # Page utilization at PEAK occupancy (end-of-run the
                # pool has drained and the ratio is vacuously 0).
                st = engine.kv_page_stats()
                if st["pages_in_use"] > peak_pages:
                    peak_pages = st["pages_in_use"]
                    page_util = (engine._live_tokens()
                                 / float(st["pages_in_use"]
                                         * st["page_len"]))
    wall = max(time.time() - t0, 1e-9)

    toks_out = sum(len(r.tokens) for r in reqs)
    ttft = [r.first_token_time - r.submit_time for r in reqs]
    per_tok = [(r.finish_time - r.first_token_time) /
               max(len(r.tokens) - 1, 1) for r in reqs]
    # Close the measured window: every windowed number below (chunks,
    # decode_seconds, occupancy, latency percentiles, accept stats)
    # describes exactly the timed stream.
    m = engine.metrics(reset=True)
    telemetry = engine.telemetry_snapshot()
    # Perf X-ray export (telemetry/xray.py): per-program XLA cost/memory
    # analysis + roofline/HBM ledger. Materialization AOT-compiles the
    # non-dispatched programs, so it happens HERE — after the measured
    # window closed, before the sequential baseline is timed.
    perf_xray = engine.perf_xray()
    profile_dir = os.environ.get(PROFILE_DIR_ENV)
    if profile_dir:
        # The profiler capture landed under profile_dir via
        # profile_window above; add the Chrome trace of the request
        # lifecycle spans next to it (Perfetto loads both).
        os.makedirs(profile_dir, exist_ok=True)
        telemetry["trace_file"] = engine.write_trace(
            os.path.join(profile_dir, "serving_trace.json"))

    # Sequential baseline: the same prompts, one at a time, greedy — the
    # pre-continuous-batching serving story. Warm each distinct length
    # first so both sides are timed at their compiled steady state.
    for n in sorted(set(lens)):
        generate(model, params, prompts[lens.index(n)][None], max_new,
                 temperature=0.0)
    tb = time.time()
    for p in prompts:
        np.asarray(generate(model, params, p[None], max_new,
                            temperature=0.0))
    seq_wall = max(time.time() - tb, 1e-9)
    seq_tok_per_sec = toks_out / seq_wall
    tok_per_sec = toks_out / wall

    # Kernel A/B attribution: which decode-attention path served, its
    # planned tile, and the isolated per-step op time — probed at the
    # step's ACTUAL query width (spec_k+1 under speculation: the verify
    # lane is the step shape the kernel serves).
    g = engine._gcfg
    if paged_on:
        # Arena planes are [L, P, H, page_len, D]; the logical per-row
        # plane is page_len * pages-per-slot (block-table width).
        page_len = int(engine._pool["k"].shape[3])
        plane_len = page_len * int(engine._pool["block_tbl"].shape[1])
    else:
        page_len = None
        plane_len = int(engine._pool["k"].shape[3])
    s_probe = engine.config.spec_k + 1 if spec_on else 1
    attn_ms, engaged = _decode_attention_probe(engine, s=s_probe)
    if not engaged:
        block_k = None
    elif paged_on:
        block_k = page_len   # kernel blocks == pages by construction
    else:
        block_k = da.planned_block_k(
            serve_cfg["max_slots"], g.n_head, s_probe, plane_len,
            g.n_embd // g.n_head, g.dtype)
    # Windowed snapshot: chunks/decode_seconds already exclude warmup.
    decode_steps = m["chunks"] * serve_cfg["chunk_size"]
    decode_s = m["decode_seconds"]

    name = "gpt2_{}_serving_tokens_per_sec".format(
        "355m" if on_tpu else "tiny_smoke" if smoke else "tiny")
    if flash_decode is False:
        # A/B runs must not share a metric series with the default
        # (kernel-on) one.
        name += "_noflashdecode"
    if not spec_decode:
        name += "_nospecdecode"
    if not int8_kv:
        name += "_noint8kv"
    if not prefix_cache:
        name += "_noprefixcache"
    if not host_offload:
        name += "_nohostoffload"
    if not paged_kv:
        name += "_nopagedkv"
    _note_trace(engine)
    return {
        "metric": name,
        "value": round(tok_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(tok_per_sec / seq_tok_per_sec, 4),
        "extra": {
            "platform": platform,
            "requests": n_req,
            "arrival_rate_per_sec": rate,
            "max_new_tokens": max_new,
            "tokens_out": toks_out,
            "p50_per_token_latency_ms": round(
                float(np.percentile(per_tok, 50)) * 1e3, 3),
            "p99_per_token_latency_ms": round(
                float(np.percentile(per_tok, 99)) * 1e3, 3),
            "p50_ttft_ms": round(float(np.percentile(ttft, 50)) * 1e3, 3),
            "p99_ttft_ms": round(float(np.percentile(ttft, 99)) * 1e3, 3),
            "p50_queue_wait_ms": m["queue_wait_p50_ms"],
            "p99_queue_wait_ms": m["queue_wait_p99_ms"],
            "slot_occupancy": round(m["slot_occupancy"], 4),
            "sequential_tokens_per_sec": round(seq_tok_per_sec, 1),
            "compile_count": m["compile_count"],
            "recompiles_after_warmup": m["recompiles"],
            "max_slots": serve_cfg["max_slots"],
            "chunk_size": serve_cfg["chunk_size"],
            "prefill_chunk": m["prefill_chunk"],
            "spec_decode": spec_on,
            "int8_kv": int8_on,
            "prefix_cache": prefix_on,
            "host_offload": offload_on,
            "adapter": m.get("adapter"),
            "paged": paged_on,
            "page_len": m.get("kv_page_len"),
            "kv_pages_total": m.get("kv_pages_total"),
            "kv_pages_peak": peak_pages if paged_on else None,
            "kv_page_utilization": (round(page_util, 4)
                                    if page_util is not None else None),
            "prefix_hit_rate": m.get("prefix_hit_rate"),
            "kv_bytes_per_slot": m.get("kv_bytes_per_slot"),
            "kv_bytes_aliased": m.get("kv_bytes_aliased"),
            "effective_slots": m.get("effective_slots"),
            "swap_outs": m.get("swap_outs"),
            "swap_ins": m.get("swap_ins"),
            "spec_k": m.get("spec_k"),
            "spec_ngram": m.get("spec_ngram"),
            "accepted_per_step_mean": m.get("accepted_per_step_mean"),
            "accepted_per_step_p50": m.get("accepted_per_step_p50"),
            "accepted_per_step_p99": m.get("accepted_per_step_p99"),
            "draft_accept_rate": m.get("draft_accept_rate"),
            "flash_decode": engaged,
            "decode_block_k": block_k,
            "kv_plane_len": plane_len,
            "decode_attention_ms_per_layer": round(attn_ms, 4),
            "decode_attention_ms_per_step": round(attn_ms * g.n_layer, 4),
            "decode_ms_per_token": round(
                decode_s / max(decode_steps, 1) * 1e3, 4),
            "telemetry": telemetry,
            "perf_xray": perf_xray,
        },
    }


def main_serve(smoke=False, flash_decode=None, spec_decode=True,
               int8_kv=True, prefix_cache=True, host_offload=True,
               paged_kv=True):
    if not smoke:
        _require_tpu_or_exit()
    _emit(_measure_serving(smoke=smoke, flash_decode=flash_decode,
                           spec_decode=spec_decode, int8_kv=int8_kv,
                           prefix_cache=prefix_cache,
                           host_offload=host_offload,
                           paged_kv=paged_kv))
    return 0


def _measure_sustained(smoke=False):
    """`bench.py --sustained`: the sustained-load harness end to end.

    Where --serve answers "how fast is one short stream", this answers
    the serving questions that only show up over TIME and LOAD: the
    windowed TTFT/ITL p50/p99, queue-depth and slot-occupancy CURVES
    (deepspeed_tpu/loadgen/ + telemetry.TimeseriesCollector), the SLO/
    goodput verdict, a stepped-arrival-rate saturation sweep reporting
    the max sustainable rate, and an A/A self-check of the noise-aware
    regression gate. ``smoke`` sizes everything for a CPU/CI second or
    two — same code path, same report schema, toy numbers; its SLO
    budgets are deliberately generous (schema-exercise values, not
    service targets) so a loaded CI box still produces a non-null
    max_sustainable_rate. See docs/BENCHMARKING.md for how to use two
    of these reports in an honest A/B."""
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu as deepspeed
    from deepspeed_tpu.loadgen import (
        SLO,
        SustainedRunner,
        WorkloadSpec,
        build_report,
        regression_gate,
        saturation_sweep,
    )
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel

    platform = jax.default_backend()
    on_tpu = platform == "tpu" and not smoke
    if on_tpu:
        cfg = GPT2Config.gpt2_medium(dropout=0.0, use_flash_attention=True)
        serve_cfg = {"max_slots": 16, "max_len": 1024, "chunk_size": 16,
                     "max_queue": 128, "int8_kv": True,
                     "prefix_cache": True, "host_offload": True}
        # prefix_pool: a handful of shared system prompts with Zipf
        # reuse — the traffic shape the shared-prefix cache exploits;
        # its hit rate lands in the report via serve_cfg + metrics.
        base = dict(arrival="poisson", rate=12.0, n_requests=96,
                    prompt_dist="lognormal", prompt_mean=64,
                    prompt_max=256, output_dist="lognormal",
                    output_mean=96, output_min=8, output_max=256,
                    prefix_pool=4, prefix_tokens=32,
                    vocab_size=cfg.vocab_size, seed=17)
        window_s, slo = 2.0, SLO(ttft_p99_ms=1500.0, itl_p99_ms=150.0)
        sweep_rates, sweep_n = (8.0, 12.0, 16.0, 24.0), 48
    else:
        cfg = GPT2Config.tiny(dropout=0.0, use_flash_attention=False)
        serve_cfg = {"max_slots": 4, "max_len": 64, "chunk_size": 4,
                     "max_queue": 64, "int8_kv": True,
                     "prefix_cache": True, "host_offload": True,
                     "prefix_slots": 4, "prefix_len": 16,
                     "min_prefix_len": 4}
        # Dense enough that every window carries completions (the
        # acceptance bar: >= 3 windows with real percentiles), short
        # enough for tier-1.
        base = dict(arrival="poisson", rate=60.0, n_requests=48,
                    prompt_dist="lognormal", prompt_mean=8, prompt_max=16,
                    output_dist="lognormal", output_mean=6, output_min=2,
                    output_max=12, prefix_pool=2, prefix_tokens=8,
                    vocab_size=cfg.vocab_size, seed=17)
        window_s = 0.1
        # Schema-exercise budgets: wide enough that CPU jitter never
        # nulls the sweep, tight enough that a wedged engine still fails.
        slo = SLO(ttft_p99_ms=10000.0, itl_p99_ms=2000.0)
        sweep_rates, sweep_n = (30.0, 60.0, 120.0), 16

    model = GPT2LMHeadModel(cfg)
    rng = np.random.RandomState(0)
    init_ids = rng.randint(0, cfg.vocab_size, size=(2, 16))
    params = model.init(jax.random.PRNGKey(0),
                        jnp.asarray(init_ids))["params"]
    engine = deepspeed.init_inference(
        model=model, params=params, config={"inference": serve_cfg})

    # Warmup: compile the mixed-step program, freeze the compile total,
    # open a fresh metrics window. From collector.start() on, the
    # registry's window state belongs to the collector (timeseries.py) —
    # no engine.metrics(reset=True) until the run's report is built.
    engine.generate([np.arange(1, 9, dtype=np.int32)], max_new_tokens=2)
    engine.recompile_detector.mark_warm()
    engine.metrics(reset=True)

    # SLO burn-rate alerting rides along (telemetry/alerts.py): each
    # run's AlertManager watches the runner's own collector with the
    # run's SLO budgets as rule budgets; every rising edge lands in
    # RunResult.alerts_fired and the artifact's trace_summary.
    from deepspeed_tpu.telemetry import AlertManager, default_rules
    alert_managers = []

    def run_spec(spec):
        runner = SustainedRunner(engine, spec, window_seconds=window_s,
                                 max_steps=500_000)
        runner.alerts = AlertManager(
            runner.collector,
            default_rules(ttft_budget_s=slo.ttft_p99_ms / 1000.0,
                          itl_budget_s=slo.itl_p99_ms / 1000.0,
                          queue_saturation=serve_cfg["max_queue"]))
        alert_managers.append(runner.alerts)
        result = runner.run()
        return build_report(
            spec, result, slo, platform=platform,
            extra={"git_hash": _git_state(),
                   "model": "gpt2_medium" if on_tpu else "gpt2_tiny",
                   "serve_cfg": dict(serve_cfg)})

    report = run_spec(WorkloadSpec(**base))

    # Saturation sweep: step the offered rate on the SAME warm engine
    # (capacity, not compile time), shorter streams per step.
    def sweep_step(rate):
        return run_spec(WorkloadSpec(**dict(
            base, rate=rate, n_requests=sweep_n, seed=int(rate) + 1000)))

    report["saturation"] = saturation_sweep(
        sweep_step, sweep_rates,
        attainment_floor=0.95 if on_tpu else 0.5)
    # Perf X-ray section: per-program cost/memory model for THIS report's
    # engine — the regression gate compares two reports' cost models
    # without hardware (a bytes/token increase flags on CPU). Stamped
    # BEFORE the A/A self-check so the self-check exercises the
    # cost-model gate too.
    report["perf_xray"] = engine.perf_xray()
    # A/A self-check: the gate against the report itself must pass (delta
    # is exactly 0 everywhere) — stamped so every report proves its own
    # gate is not trivially red.
    report["gate_self_check"] = regression_gate(report, report)
    _note_trace(engine, alerts_fired=[
        r["rule"] for m in alert_managers for r in m.fired()])

    agg = report["aggregate"]
    return {
        "metric": "gpt2_{}_sustained_goodput_tokens_per_sec_per_chip"
                  .format("355m" if on_tpu else "tiny_smoke"),
        "value": round(agg["goodput_tokens_per_sec_per_chip"], 1),
        "unit": "tokens/s/chip",
        # No sequential baseline here — goodput is an absolute serving
        # number; A/B happens between two reports via the gate.
        "vs_baseline": None,
        "extra": {
            "platform": platform,
            "note": "windowed SLO report under 'sustained'; compare two "
                    "runs with loadgen.regression_gate (see "
                    "docs/BENCHMARKING.md)",
            "sustained": report,
        },
    }


def main_sustained(smoke=False):
    if not smoke:
        _require_tpu_or_exit()
    _emit(_measure_sustained(smoke=smoke))
    return 0


def _measure_chaos(smoke=False):
    """`bench.py --chaos-smoke`: the recovery invariant under load, as a
    benchmark artifact.

    One sustained run with a FaultPlan armed MID-RUN (loadgen chaos
    mode): a fatal step fault fires against a live mixed batch, the
    engine rebuilds its device state and replays every in-flight
    request (docs/RESILIENCE.md). The run then ASSERTS the invariant —
    the fault actually fired, at least one recovery happened, zero
    accepted requests were lost — and stamps the recovery facts
    (recovery_time_s, requests_lost, the SLO attainment split during/
    outside recovery) into the JSON. ``smoke`` is the tiny-CPU tier-1
    shape; on TPU the same path runs gpt2-medium."""
    import math

    import jax
    import jax.numpy as jnp

    import deepspeed_tpu as deepspeed
    from deepspeed_tpu.inference import Fault, FaultPlan
    from deepspeed_tpu.loadgen import (
        SLO,
        SustainedRunner,
        WorkloadSpec,
        build_report,
    )
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel

    platform = jax.default_backend()
    on_tpu = platform == "tpu" and not smoke
    if on_tpu:
        cfg = GPT2Config.gpt2_medium(dropout=0.0, use_flash_attention=True)
        serve_cfg = {"max_slots": 16, "max_len": 1024, "chunk_size": 16,
                     "max_queue": 128, "fault_injection": True}
        spec = WorkloadSpec(arrival="poisson", rate=12.0, n_requests=64,
                            prompt_dist="lognormal", prompt_mean=64,
                            prompt_max=256, output_dist="lognormal",
                            output_mean=96, output_min=8, output_max=256,
                            vocab_size=cfg.vocab_size, seed=23)
        window_s, slo = 2.0, SLO(ttft_p99_ms=1500.0, itl_p99_ms=150.0)
    else:
        cfg = GPT2Config.tiny(dropout=0.0, use_flash_attention=False)
        serve_cfg = {"max_slots": 4, "max_len": 64, "chunk_size": 4,
                     "max_queue": 64, "fault_injection": True}
        # Long enough output streams that the fault lands mid-decode
        # with several requests in flight — recovery with real replays.
        spec = WorkloadSpec(arrival="poisson", rate=60.0, n_requests=32,
                            prompt_dist="lognormal", prompt_mean=8,
                            prompt_max=16, output_dist="lognormal",
                            output_mean=8, output_min=4, output_max=12,
                            vocab_size=cfg.vocab_size, seed=23)
        window_s = 0.1
        slo = SLO(ttft_p99_ms=10000.0, itl_p99_ms=2000.0)

    model = GPT2LMHeadModel(cfg)
    rng = np.random.RandomState(0)
    init_ids = rng.randint(0, cfg.vocab_size, size=(2, 16))
    params = model.init(jax.random.PRNGKey(0),
                        jnp.asarray(init_ids))["params"]
    engine = deepspeed.init_inference(
        model=model, params=params, config={"inference": serve_cfg})
    engine.generate([np.arange(1, 9, dtype=np.int32)], max_new_tokens=2)
    engine.recompile_detector.mark_warm()
    engine.metrics(reset=True)

    # ONE fatal step fault, two steps after arming (arming waits for the
    # first window, so the batch is live when it fires).
    plan = FaultPlan(faults=(Fault("raise", step=2),))
    runner = SustainedRunner(engine, spec, window_seconds=window_s,
                             max_steps=500_000, chaos_plan=plan,
                             chaos_after_s=window_s / 2)
    result = runner.run()
    report = build_report(
        spec, result, slo, platform=platform,
        extra={"git_hash": _git_state(),
               "model": "gpt2_medium" if on_tpu else "gpt2_tiny",
               "serve_cfg": dict(serve_cfg),
               "fault_plan": {"faults": [
                   {"kind": f.kind, "step": f.step,
                    "duration_steps": f.duration_steps}
                   for f in plan.faults], "seed": plan.seed}})
    chaos = report["chaos"]
    post = engine.metrics()

    # The invariant, asserted in the artifact's own build: the fault
    # fired, recovery ran, nothing was lost, the engine came back
    # healthy, and the rebuild reused the compiled program.
    assert chaos["faults_injected"] >= 1, "fault never fired"
    assert chaos["recoveries"] >= 1, "no recovery recorded"
    assert chaos["requests_lost"] == 0, \
        "recovery lost {} request(s)".format(chaos["requests_lost"])
    assert math.isfinite(chaos["recovery_time_s"])
    assert engine.health == "healthy" and engine.idle
    assert post["compile_count"] == 1, \
        "recovery recompiled: {}".format(post["compile_count"])

    # Observability gate (docs/OBSERVABILITY.md): a request the fault
    # interrupted mid-stream must autopsy as lost-then-replayed with a
    # contiguous hop chain — the trace proves the recovery story, not
    # just the counters.
    from deepspeed_tpu.telemetry import build_autopsy
    replayed_tids = sorted({ev["tid"] for ev in engine.tracer.events()
                            if ev["name"] == "request/replayed"})
    assert replayed_tids, "recovery replayed but left no trace event"
    autopsy = build_autopsy(engine.trace_recorders(), replayed_tids[0])
    assert autopsy["replays"] >= 1, "autopsy missed the replay"
    assert autopsy["terminal"]["cause"] == "done", \
        "replayed request did not finish: {}".format(autopsy["terminal"])
    assert autopsy["terminal"]["lost_then_replayed"], \
        "autopsy did not mark the request lost-then-replayed"
    assert autopsy["hop_gaps"] == [], \
        "hop sequence has gaps: {}".format(autopsy["hop_gaps"])
    _note_trace(engine)

    return {
        "metric": "gpt2_{}_chaos_recovery_time_s".format(
            "355m" if on_tpu else "tiny_smoke"),
        "value": round(chaos["recovery_time_s"], 6),
        "unit": "s",
        "vs_baseline": None,
        "extra": {
            "platform": platform,
            "requests_lost": chaos["requests_lost"],
            "recoveries": chaos["recoveries"],
            "faults_injected": chaos["faults_injected"],
            "requests_replayed": sum(
                r["replayed"] for r in chaos["recovery_intervals"]),
            "slo_attainment_during_recovery":
                chaos["slo_attainment_during_recovery"],
            "slo_attainment_outside_recovery":
                chaos["slo_attainment_outside_recovery"],
            "note": "one injected fatal step fault mid-run; full windowed "
                    "report under 'chaos_report' (docs/RESILIENCE.md)",
            "replay_autopsy": {
                "tid": replayed_tids[0],
                "replays": autopsy["replays"],
                "hops": len(autopsy["hops"]),
                "hop_gaps": autopsy["hop_gaps"],
                "terminal": autopsy["terminal"],
            },
            "chaos_report": report,
        },
    }


def main_chaos(smoke=False):
    if not smoke:
        _require_tpu_or_exit()
    _emit(_measure_chaos(smoke=smoke))
    return 0


def _measure_fleet(smoke=False, prefix_affinity=True):
    """`bench.py --fleet-smoke`: the FLEET failover invariant as a
    benchmark artifact.

    A 2-replica ServingFleet (real per-replica stepping threads) serves
    a mixed greedy/sampled/spec request stream; once replica 0 is
    mid-stream (it owns live requests with tokens already emitted), a
    fatal fault kills it (recovery_max_retries=0 -> dead on the first
    failure) and its requests fail over to replica 1 with residual
    budgets. The artifact build ASSERTS the invariant: zero requests
    lost, every stream bit-identical to a fault-free single-engine
    reference, the survivor's compile_count unchanged, and the fleet
    healthy at exit — then stamps the facts machine-readable.

    The stream is template-heavy (a small shared-prefix pool ahead of
    unique tails) and the replicas run the prefix cache, so the
    artifact also stamps the FLEET prefix hit rate; ``--no-prefix-
    affinity`` (suffix ``_noprefixaffinity``) is the directory-off side
    of that A/B — same stream, same caches, no fleet-level affinity or
    adoption."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference import (
        Fault,
        FaultPlan,
        InferenceConfig,
        InferenceEngine,
        ServingFleet,
    )
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel

    platform = jax.default_backend()
    on_tpu = platform == "tpu" and not smoke
    if on_tpu:
        cfg = GPT2Config.gpt2_medium(dropout=0.0, use_flash_attention=True)
        serve_cfg = {"max_slots": 8, "max_len": 512, "chunk_size": 8,
                     "prefill_chunk": 16, "max_queue": 64,
                     "spec_decode": True, "spec_k": 2, "spec_ngram": 2,
                     "fault_injection": True, "recovery_max_retries": 0,
                     "prefix_cache": True, "prefix_slots": 8,
                     "prefix_len": 64, "min_prefix_len": 8}
        n_requests, max_new, template_len = 24, 48, 24
    else:
        cfg = GPT2Config.tiny(dropout=0.0, use_flash_attention=False)
        serve_cfg = {"max_slots": 2, "max_len": 64, "chunk_size": 2,
                     "prefill_chunk": 4, "max_queue": 32,
                     "spec_decode": True, "spec_k": 2, "spec_ngram": 2,
                     "fault_injection": True, "recovery_max_retries": 0,
                     "prefix_cache": True, "prefix_slots": 4,
                     "prefix_len": 16, "min_prefix_len": 4}
        n_requests, max_new, template_len = 8, 8, 8

    model = GPT2LMHeadModel(cfg)
    rng = np.random.RandomState(0)
    init_ids = rng.randint(0, cfg.vocab_size, size=(2, 16))
    params = model.init(jax.random.PRNGKey(0),
                        jnp.asarray(init_ids))["params"]

    # The fixed request stream: greedy and sampled interleaved, a third
    # of them opting out of speculation — the full mixed-batch surface.
    # Template-heavy shape: two shared prompt templates ahead of short
    # unique tails, so the prefix cache (and, fleet-side, the prefix
    # directory + affinity routing) has real reuse to exploit.
    req_rng = np.random.RandomState(11)
    templates = req_rng.randint(0, cfg.vocab_size,
                                size=(2, template_len))
    requests = [
        {"prompt": np.concatenate(
            [templates[i % 2],
             req_rng.randint(0, cfg.vocab_size,
                             size=4 + (i % 5))]).astype(np.int32),
         "max_new_tokens": max_new,
         "temperature": 0.0 if i % 2 == 0 else 0.7,
         "seed": 1000 + i,
         "spec_decode": (i % 3 != 0)}
        for i in range(n_requests)]

    def submit_all(target, reqs):
        return [target.submit(r["prompt"],
                              max_new_tokens=r["max_new_tokens"],
                              temperature=r["temperature"],
                              seed=r["seed"],
                              spec_decode=r["spec_decode"])
                for r in reqs]

    # Reference: the same stream on one fault-free engine. The
    # positional fold_in(seed, pos) rng makes every stream a pure
    # function of (prompt, seed, params) — whatever replica, batch mix,
    # or failover timing the fleet run sees, tokens must match this.
    ref_engine = InferenceEngine(
        model, params, config=InferenceConfig.from_dict(
            dict(serve_cfg, fault_injection=False)))
    ref_handles = submit_all(ref_engine, requests)
    ref_engine.run()
    reference = [list(h.tokens) for h in ref_handles]

    fleet = ServingFleet(model, params, n_replicas=2,
                         config=InferenceConfig.from_dict(serve_cfg),
                         window_seconds=0.1, seed=0,
                         prefix_affinity=prefix_affinity)
    t0 = time.time()
    wave1 = submit_all(fleet, requests[:n_requests // 2])

    # Kill replica 0 MID-STREAM: wait until it owns a live request with
    # tokens already emitted (so failover really resumes a partial
    # stream), then arm one fatal fault. recovery_max_retries=0 turns
    # the first failure into dead.
    deadline = time.time() + 60.0
    while time.time() < deadline:
        # Replica 0 mid-stream AND the survivor already warm (its
        # compile count is the invariant's baseline — read it after
        # its first step, not mid-compile).
        if (any(fr.replica_id == 0 and not fr.done and len(fr.tokens) > 0
                for fr in wave1)
                and fleet.compile_counts[1] >= 1):
            break
        time.sleep(0.001)
    mid_stream = [
        {"fid": fr.fid, "tokens_emitted": len(fr.tokens)}
        for fr in wave1 if fr.replica_id == 0 and not fr.done]
    survivor_compiles_pre = fleet.compile_counts[1]
    fleet.inject_faults(FaultPlan(faults=(Fault("raise", step=0),)),
                        replica=0)
    # Second wave lands while the kill is in flight — routing must keep
    # absorbing traffic on the survivor.
    wave2 = submit_all(fleet, requests[n_requests // 2:])
    handles = wave1 + wave2
    settled = fleet.wait_idle(timeout_s=300.0)
    wall_s = time.time() - t0

    got = [list(fr.tokens) for fr in handles]
    lost = sum(1 for fr in handles
               if fr.phase not in ("done", "expired", "cancelled"))
    mismatched = [i for i, (g, r) in enumerate(zip(got, reference))
                  if g != r]
    dead = [rep.rid for rep in fleet.replicas if not rep.alive]
    fleet_metrics = fleet.metrics()["fleet"]
    prefix_hit_rate = fleet.prefix_hit_rate()
    compile_counts = fleet.compile_counts
    health = fleet.health

    # Observability gate (docs/OBSERVABILITY.md): the autopsy of a
    # killed-mid-stream request must show the WHOLE failover chain —
    # old owner's failover_out, the orphan pump's re-home, the
    # survivor's failover_in — with zero gaps in the hop sequence.
    moved = [fr for fr in wave1 if fr.failovers > 0]
    assert moved, "kill landed but no wave-1 request records a failover"
    autopsy = fleet.explain(moved[0])
    names = [h["name"] for h in autopsy["hops"]]
    assert autopsy["failovers"] >= 1, "autopsy missed the failover"
    assert "request/failover_out" in names and \
        "request/failover_in" in names, \
        "failover chain incomplete in trace: {}".format(names)
    assert names.index("request/failover_out") < \
        names.index("request/failover_in"), "failover hops out of order"
    out_site = autopsy["hops"][names.index("request/failover_out")]["site"]
    in_site = autopsy["hops"][names.index("request/failover_in")]["site"]
    assert out_site == "replica0" and in_site != out_site, \
        "failover arrow does not cross replicas: {} -> {}".format(
            out_site, in_site)
    assert autopsy["hop_gaps"] == [], \
        "hop sequence has gaps: {}".format(autopsy["hop_gaps"])
    assert autopsy["terminal"]["cause"] == "done" and \
        autopsy["terminal"]["lost_then_replayed"], \
        "killed-mid-stream request did not finish via rescue: {}".format(
            autopsy["terminal"])
    _note_trace(fleet)
    fleet.close()

    # The invariant, asserted in the artifact's own build.
    assert settled, "fleet did not settle idle"
    assert lost == 0, "failover lost {} request(s)".format(lost)
    assert not mismatched, \
        "streams diverged from the fault-free reference: {}".format(
            mismatched)
    assert dead == [0], "expected exactly replica 0 dead, got {}".format(
        dead)
    assert fleet_metrics["failovers"] >= 1, "no request failed over"
    assert compile_counts[1] == survivor_compiles_pre, \
        "survivor recompiled during failover: {} -> {}".format(
            survivor_compiles_pre, compile_counts[1])
    assert health == "healthy", "fleet unhealthy at exit: {}".format(
        health)

    name = "gpt2_{}_fleet_failover_wall_s".format(
        "355m" if on_tpu else "tiny_smoke")
    if not prefix_affinity:
        # A/B runs must not share a metric series with the
        # affinity-on one.
        name += "_noprefixaffinity"
    return {
        "metric": name,
        "value": round(wall_s, 6),
        "unit": "s",
        "vs_baseline": None,
        "extra": {
            "platform": platform,
            "n_replicas": 2,
            "n_requests": n_requests,
            "requests_lost": lost,
            "bit_identical": not mismatched,
            "prefix_affinity": bool(prefix_affinity),
            "fleet_prefix_hit_rate": round(prefix_hit_rate, 4),
            "prefix_hits": int(fleet_metrics.get("prefix_hits", 0)),
            "prefix_misses": int(fleet_metrics.get("prefix_misses", 0)),
            "prefix_adoptions": int(
                fleet_metrics.get("prefix_adoptions", 0)),
            "prefix_bytes_shipped": int(
                fleet_metrics.get("prefix_bytes_shipped", 0)),
            "affinity_routed": int(
                fleet_metrics.get("affinity_routed", 0)),
            "prefix_directory": fleet_metrics.get("prefix_directory"),
            "failovers": fleet_metrics["failovers"],
            "dead_replicas": dead,
            "mid_stream_at_kill": mid_stream,
            "failover_autopsy": {
                "tid": autopsy["tid"],
                "failovers": autopsy["failovers"],
                "hops": len(autopsy["hops"]),
                "chain": [out_site, "fleet", in_site],
                "hop_gaps": autopsy["hop_gaps"],
                "terminal": autopsy["terminal"],
            },
            "survivor_compile_counts": {
                k: v for k, v in compile_counts.items() if k != 0},
            "fleet_health_at_exit": health,
            "breaker_states": fleet_metrics["breaker_states"],
            "serve_cfg": dict(serve_cfg),
            "note": "replica 0 killed mid-stream; docs/RESILIENCE.md "
                    "'Serving fleet' section is the contract",
        },
    }


def main_fleet(smoke=False, prefix_affinity=True):
    if not smoke:
        _require_tpu_or_exit()
    _emit(_measure_fleet(smoke=smoke, prefix_affinity=prefix_affinity))
    return 0


def _measure_disagg(smoke=False, disagg=True):
    """`bench.py --fleet-smoke --disagg`: the disaggregation ITL A/B as
    a benchmark artifact.

    A 3-replica fleet (1 prefill + 2 decode under --disagg; the same
    three replicas all-mixed under --no-disagg, metric suffixed
    _nodisagg) serves one seeded open-loop stream of long-prompt
    requests. On the mixed side every replica's decode steps share the
    step program with live prefill lanes — each chunk of someone else's
    prompt rides the same dispatch, inflating inter-token latency for
    every decoding request in the batch. On the disagg side decode
    replicas never run a prefill lane (prompts arrive as finished KV
    planes via handoff), so their ITL reflects decode work alone. The
    artifact stamps ITL p50/p99 plus the handoff counters, and asserts
    the run itself was sound: zero requests lost, no re-prefill
    fallbacks, one compile per replica. The strictly-lower-p99
    acceptance is pinned in tests/unit/test_disagg.py, which runs both
    sides in one process."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference import InferenceConfig, ServingFleet
    from deepspeed_tpu.loadgen import (
        SLO,
        SustainedRunner,
        WorkloadSpec,
        build_report,
    )
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel

    platform = jax.default_backend()
    on_tpu = platform == "tpu" and not smoke
    if on_tpu:
        cfg = GPT2Config.gpt2_medium(dropout=0.0, use_flash_attention=True)
        serve_cfg = {"max_slots": 8, "max_len": 512, "chunk_size": 8,
                     "prefill_chunk": 16, "max_queue": 128}
        base = dict(arrival="poisson", rate=12.0, n_requests=64,
                    prompt_dist="lognormal", prompt_mean=192,
                    prompt_max=384, output_dist="fixed", output_mean=48,
                    output_max=48, vocab_size=cfg.vocab_size, seed=23)
        window_s, slo = 2.0, SLO(ttft_p99_ms=2000.0, itl_p99_ms=200.0)
    else:
        cfg = GPT2Config.tiny(dropout=0.0, use_flash_attention=False)
        # Long prompts against a small prefill_chunk: each prompt takes
        # many prefill steps, so on the mixed side decode steps almost
        # always carry a prefill lane — the interference the A/B exists
        # to expose.
        serve_cfg = {"max_slots": 4, "max_len": 96, "chunk_size": 2,
                     "prefill_chunk": 8, "max_queue": 128}
        # Outputs long enough (23 inter-token gaps) that the one
        # handoff gap per request amortizes instead of dominating the
        # per-request ITL.
        base = dict(arrival="poisson", rate=60.0, n_requests=24,
                    prompt_dist="fixed", prompt_mean=32, prompt_max=48,
                    output_dist="fixed", output_mean=24, output_max=24,
                    vocab_size=cfg.vocab_size, seed=23)
        window_s = 0.1
        # Schema-exercise budgets (CPU jitter; the A/B compares the two
        # sides, not either side against the SLO).
        slo = SLO(ttft_p99_ms=30000.0, itl_p99_ms=10000.0)

    model = GPT2LMHeadModel(cfg)
    rng = np.random.RandomState(0)
    init_ids = rng.randint(0, cfg.vocab_size, size=(2, 16))
    params = model.init(jax.random.PRNGKey(0),
                        jnp.asarray(init_ids))["params"]

    roles = ("prefill", "decode", "decode") if disagg else None
    # idle_wait_s: an idle decode replica polls the handoff pump at this
    # cadence — the default 10ms is a visible slice of a tiny-model
    # inter-token gap, so the smoke tightens it.
    fleet = ServingFleet(model, params, n_replicas=3,
                         config=InferenceConfig.from_dict(serve_cfg),
                         window_seconds=window_s, seed=0, roles=roles,
                         idle_wait_s=0.01 if on_tpu else 0.002)
    # Warmup (the SustainedRunner contract: the caller owns compile).
    # Six short requests spread across the least-loaded routing so every
    # replica compiles BEFORE the measured stream — on the disagg side a
    # decode replica compiles on its first adoption, and an un-warmed
    # acceptor stalls the handoff pump (and with it the prefill replica)
    # for the whole compile, which would poison the first window of the
    # A/B on both sides.
    warm_rng = np.random.RandomState(7)
    for i in range(6):
        fleet.submit(
            warm_rng.randint(
                0, cfg.vocab_size,
                size=int(base["prompt_mean"])).astype(np.int32),
            max_new_tokens=8, temperature=0.0, seed=900 + i)
    assert fleet.wait_idle(timeout_s=300.0), "warmup did not settle"
    assert all(c == 1 for c in fleet.compile_counts.values()), \
        "warmup left a cold replica: {}".format(fleet.compile_counts)
    fleet.metrics(reset=True)
    spec = WorkloadSpec(**base)
    # The runner reads counter DELTAS for the report's disagg section;
    # mirror that for handoffs_in so warmup traffic stays out of the
    # stamped numbers.
    handoffs_in_start = int(fleet.counters["handoffs_in"])
    runner = SustainedRunner(fleet, spec, window_seconds=window_s,
                             max_steps=500_000)
    result = runner.run()
    handoffs_in = int(fleet.counters["handoffs_in"]) - handoffs_in_start
    report = build_report(
        spec, result, slo, platform=platform,
        extra={"git_hash": _git_state(),
               "model": "gpt2_medium" if on_tpu else "gpt2_tiny",
               "serve_cfg": dict(serve_cfg),
               "roles": list(fleet.roles)})
    compile_counts = fleet.compile_counts
    health = fleet.health
    _note_trace(fleet)
    fleet.close()

    # Soundness of the run itself (the cross-side comparison lives in
    # tests/unit/test_disagg.py).
    assert result.requests_lost == 0, \
        "disagg run lost {} request(s)".format(result.requests_lost)
    assert result.shed == 0, "queue shed {} request(s)".format(result.shed)
    assert health == "healthy", "fleet unhealthy at exit: {}".format(
        health)
    assert all(c == 1 for c in compile_counts.values()), \
        "expected one compile per replica, got {}".format(compile_counts)
    if disagg:
        assert result.handoffs > 0, "disagg run performed no handoffs"
        assert result.handoff_fallbacks == 0, \
            "{} re-prefill fallback(s) in a fault-free run".format(
                result.handoff_fallbacks)
    else:
        assert result.handoffs == 0, \
            "all-mixed fleet performed {} handoff(s)".format(
                result.handoffs)

    agg = report["aggregate"]
    name = "gpt2_{}_disagg_decode_itl_p99_ms".format(
        "355m" if on_tpu else "tiny_smoke")
    if not disagg:
        # A/B runs must not share a metric series with the
        # disagg-on one.
        name += "_nodisagg"
    return {
        "metric": name,
        "value": round(agg["itl_p99_ms"], 3),
        "unit": "ms",
        "vs_baseline": None,
        "extra": {
            "platform": platform,
            "disagg": bool(disagg),
            "roles": list(fleet.roles),
            "n_requests": int(base["n_requests"]),
            "offered_rate": float(base["rate"]),
            "itl_p50_ms": agg["itl_p50_ms"],
            "itl_p99_ms": agg["itl_p99_ms"],
            "ttft_p99_ms": agg["ttft_p99_ms"],
            "requests_lost": int(result.requests_lost),
            "handoffs": int(result.handoffs),
            "handoffs_in": handoffs_in,
            "handoff_fallbacks": int(result.handoff_fallbacks),
            "handoff_bytes_shipped": int(result.handoff_bytes_shipped),
            "compile_counts": {str(k): v
                               for k, v in compile_counts.items()},
            "fleet_health_at_exit": health,
            "serve_cfg": dict(serve_cfg),
            "disagg_report": report["disagg"],
            "note": "ITL A/B vs the _nodisagg suffix at the same "
                    "offered rate; docs/INFERENCE.md 'Disaggregated "
                    "prefill/decode' section is the contract",
        },
    }


def main_disagg(smoke=False, disagg=True):
    if not smoke:
        _require_tpu_or_exit()
    _emit(_measure_disagg(smoke=smoke, disagg=disagg))
    return 0


def _measure_frontdoor(smoke=False, frontdoor=True):
    """`bench.py --frontdoor-smoke`: the SLO front door's priority A/B
    as a benchmark artifact.

    ONE mixed-tenant workload (loadgen WorkloadSpec.mixed_tenants): per
    tenant, a steady interactive Poisson stream plus a batch ramp that
    saturates the engine by the tail of the run. ``frontdoor=True``
    drives it through inference.FrontDoor — priority dispatch, batch
    gating, preemption into the swapped phase — and ASSERTS the
    acceptance bar: interactive p99 TTFT within its budget, zero lost,
    compile_count still 1. ``frontdoor=False`` (`--no-frontdoor`) runs
    the SAME offered load straight into the engine's FIFO (metric
    suffixed ``_nofrontdoor`` so the series never mix) with no TTFT
    assertion — interactive queues behind the batch backlog, and the
    per-class numbers stamped in ``extra`` show the budget violation
    the A/B exists to show."""
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu as deepspeed
    from deepspeed_tpu.inference import (
        FrontDoor,
        FrontDoorConfig,
        PriorityClass,
        TenantPolicy,
    )
    from deepspeed_tpu.loadgen import (
        SLO,
        SustainedRunner,
        WorkloadSpec,
        build_report,
    )
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel

    platform = jax.default_backend()
    on_tpu = platform == "tpu" and not smoke
    if on_tpu:
        cfg = GPT2Config.gpt2_medium(dropout=0.0, use_flash_attention=True)
        serve_cfg = {"max_slots": 16, "max_len": 1024, "chunk_size": 16,
                     "max_queue": 256, "host_offload": True}
        spec = WorkloadSpec.mixed_tenants(
            tenants=("tenant_a", "tenant_b"), seed=29,
            interactive_rate=4.0, interactive_n=24,
            batch_rate=24.0, batch_ramp_from=4.0, batch_n=48,
            prompt_dist="lognormal", prompt_mean=64, prompt_max=256,
            output_dist="lognormal", output_mean=64, output_min=16,
            output_max=128, vocab_size=cfg.vocab_size)
        window_s = 2.0
        budget_ms = 1500.0
    else:
        cfg = GPT2Config.tiny(dropout=0.0, use_flash_attention=False)
        # TWO slots and a deep queue: the batch ramp buries the FIFO,
        # which is exactly the head-of-line effect the front door must
        # beat (and the --no-frontdoor A/B must show).
        serve_cfg = {"max_slots": 2, "max_len": 64, "chunk_size": 4,
                     "max_queue": 256, "host_offload": True,
                     "swap_slots": 8}
        # Batch floods in almost at once (flat "ramp" at 200/s) with
        # long outputs — several seconds of work for two slots — while
        # interactive trickles across that whole saturation window.
        spec = WorkloadSpec.mixed_tenants(
            tenants=("tenant_a", "tenant_b"), seed=29,
            interactive_rate=2.0, interactive_n=8,
            batch_rate=200.0, batch_ramp_from=200.0, batch_n=60,
            prompt_dist="lognormal", prompt_mean=6, prompt_min=2,
            prompt_max=10,
            interactive_overrides={"output_dist": "fixed",
                                   "output_mean": 3},
            batch_overrides={"output_dist": "fixed", "output_mean": 32},
            vocab_size=cfg.vocab_size)
        window_s = 0.25
        # The acceptance budget: generous against CPU/CI jitter for the
        # front-door run (priority dispatch holds interactive to a slot
        # wait, well under a second), but far below the multi-second
        # head-of-line delay the batch flood inflicts on bare FIFO.
        budget_ms = 1000.0

    model = GPT2LMHeadModel(cfg)
    rng = np.random.RandomState(0)
    init_ids = rng.randint(0, cfg.vocab_size, size=(2, 16))
    params = model.init(jax.random.PRNGKey(0),
                        jnp.asarray(init_ids))["params"]
    engine = deepspeed.init_inference(
        model=model, params=params, config={"inference": serve_cfg})
    engine.generate([np.arange(1, 9, dtype=np.int32)], max_new_tokens=2)
    engine.recompile_detector.mark_warm()
    engine.metrics(reset=True)

    if frontdoor:
        target = FrontDoor(engine, FrontDoorConfig(
            classes=(
                PriorityClass("interactive", ttft_budget_ms=budget_ms,
                              weight=4.0, shed_on_budget=False),
                PriorityClass("batch", weight=1.0, preemptible=True),
            ),
            tenants=(TenantPolicy("tenant_a"), TenantPolicy("tenant_b")),
            # Keep the engine-side FIFO shallow: batch only flows while
            # a hypothetical interactive arrival would still see ~1/4
            # of its budget — the rest of the flood waits in the lanes.
            batch_headroom=0.25,
        ))
    else:
        target = engine

    slo = SLO(ttft_p99_ms=budget_ms, itl_p99_ms=None)
    class_slos = {
        "interactive": SLO(ttft_p99_ms=budget_ms, itl_p99_ms=None),
        "batch": SLO(ttft_p99_ms=None, itl_p99_ms=None),
    }
    runner = SustainedRunner(target, spec, window_seconds=window_s,
                             max_steps=500_000)
    result = runner.run()
    report = build_report(
        spec, result, slo, platform=platform, class_slos=class_slos,
        extra={"git_hash": _git_state(),
               "model": "gpt2_medium" if on_tpu else "gpt2_tiny",
               "serve_cfg": dict(serve_cfg),
               "frontdoor": bool(frontdoor),
               "budget_ms": budget_ms})
    fd_classes = report["frontdoor"]["classes"]
    inter = fd_classes.get("interactive", {})
    batch = fd_classes.get("batch", {})
    post = target.metrics() if frontdoor else engine.metrics()
    compile_count = post["compile_count"]
    _note_trace(target)

    assert result.requests_lost == 0, \
        "{} accepted request(s) lost".format(result.requests_lost)
    assert compile_count == 1, \
        "front-door run recompiled: {}".format(compile_count)
    assert batch.get("completed", 0) > 0, "batch stream never completed"
    if frontdoor:
        # The acceptance bar: interactive held its budget WHILE the
        # batch ramp saturated the engine. The --no-frontdoor A/B runs
        # the same stream and is expected to blow through it.
        p99 = inter.get("ttft_p99_ms")
        assert p99 is not None and p99 <= budget_ms, \
            "interactive p99 TTFT {}ms exceeds the {}ms budget with " \
            "the front door ON".format(p99, budget_ms)

    suffix = "" if frontdoor else "_nofrontdoor"
    extra = {
        "platform": platform,
        "frontdoor": bool(frontdoor),
        "budget_ms": budget_ms,
        "interactive_ttft_p99_ms": inter.get("ttft_p99_ms"),
        "interactive_itl_p99_ms": inter.get("itl_p99_ms"),
        "interactive_attainment": inter.get("slo_attainment"),
        "batch_ttft_p99_ms": batch.get("ttft_p99_ms"),
        "batch_itl_p99_ms": batch.get("itl_p99_ms"),
        "sheds_by_reason": report["frontdoor"]["sheds_by_reason"],
        "preemptions": int(result.preemptions),
        "preempt_resumes": int(result.preempt_resumes),
        "requests_lost": int(result.requests_lost),
        "compile_count": int(compile_count),
        "note": "per-class SLO A/B vs the _nofrontdoor suffix at the "
                "same offered load; docs/INFERENCE.md 'Streaming, "
                "SLO-aware front door' section is the contract",
        "frontdoor_report": report["frontdoor"],
    }
    if frontdoor:
        extra["frontdoor_metrics"] = post.get("frontdoor")
    return {
        "metric": "gpt2_{}_frontdoor{}_interactive_ttft_p99_ms".format(
            "355m" if on_tpu else "tiny_smoke", suffix),
        "value": (round(inter["ttft_p99_ms"], 3)
                  if inter.get("ttft_p99_ms") is not None else None),
        "unit": "ms",
        "vs_baseline": None,
        "extra": extra,
    }


def main_frontdoor(smoke=False, frontdoor=True):
    if not smoke:
        _require_tpu_or_exit()
    _emit(_measure_frontdoor(smoke=smoke, frontdoor=frontdoor))
    return 0


def main_bert(sparse=False):
    _require_tpu_or_exit()
    _measure_bert(sparse=sparse, steps=12)


def main():
    _require_tpu_or_exit()
    _emit(_measure_gpt2(batch=8, seq=1024, steps=20))


def main_sweep():
    """`bench.py --sweep`: tok/s + MFU over a {batch} x {seq} grid at 355M,
    one JSON line per config (the TPU analogue of the reference's
    tests/model/Megatron_GPT2/run_perf_baseline.py config sweep). The
    grid's rows at fixed tokens-per-step show the batch/HBM trade; the
    headline (b8 x T1024) is part of the grid. Each config runs in THIS
    process sequentially — one backend init, engines built per config."""
    _require_tpu_or_exit()
    for batch, seq in ((8, 1024), (12, 1024), (16, 1024), (4, 2048),
                       (8, 2048), (2, 4096), (4, 4096)):
        r = _measure_gpt2(batch=batch, seq=seq, steps=10)
        # Name by the ACTUAL measured config (under an explicit CPU
        # request the measurement is the tiny smoke model — the metric
        # must say so).
        r["metric"] = "sweep_{}_b{}_t{}".format(
            r["metric"], r["extra"]["batch"], r["extra"]["seq"])
        _emit(r)
        if r["extra"]["platform"] != "tpu":
            break  # off-TPU every grid entry degrades to the same smoke
    return 0


def _dispatch(argv):
    # --no-flash-decode: the einsum side of the decode-kernel A/B
    # (default None lets the engine pick — the Pallas kernel on TPU).
    # --no-spec-decode: the draft-free side of the speculative-decoding
    # A/B (default True — n-gram drafting on; metric suffixed
    # _nospecdecode so the series never mix).
    # --no-int8-kv / --no-prefix-cache / --no-host-offload: the
    # hierarchy-off sides of the KV-memory-hierarchy A/Bs (default True
    # each; metric suffixed _noint8kv / _noprefixcache / _nohostoffload
    # so the series never mix).
    # --no-prefix-affinity: the directory-off side of the fleet
    # prefix-affinity A/B (--fleet/--fleet-smoke only; metric suffixed
    # _noprefixaffinity) — per-replica caches stay on, fleet routing
    # ignores them.
    # --disagg / --no-disagg: the disaggregation ITL A/B (--fleet/
    # --fleet-smoke only). --disagg runs 1 prefill + 2 decode replicas;
    # --no-disagg runs the same three replicas all-mixed (metric
    # suffixed _nodisagg so the series never mix). Either flag routes to
    # the disagg benchmark instead of the failover one.
    flash_decode = False if "--no-flash-decode" in argv else None
    spec = "--no-spec-decode" not in argv
    int8_kv = "--no-int8-kv" not in argv
    prefix_cache = "--no-prefix-cache" not in argv
    host_offload = "--no-host-offload" not in argv
    # --no-paged-kv: the dense-pool side of the paged-KV A/B (default
    # True — page-granular pool on; metric suffixed _nopagedkv so the
    # series never mix).
    paged_kv = "--no-paged-kv" not in argv
    prefix_affinity = "--no-prefix-affinity" not in argv
    disagg_ab = "--disagg" in argv or "--no-disagg" in argv
    disagg_on = "--no-disagg" not in argv
    # --frontdoor / --no-frontdoor: the SLO front-door A/B. --frontdoor
    # drives the mixed-tenant workload through inference.FrontDoor and
    # asserts the interactive TTFT budget; --no-frontdoor runs the SAME
    # offered load straight into the engine FIFO (metric suffixed
    # _nofrontdoor so the series never mix) with no budget assertion.
    frontdoor_on = "--no-frontdoor" not in argv
    if "--frontdoor-smoke" in argv:
        return main_frontdoor(smoke=True, frontdoor=frontdoor_on)
    if "--frontdoor" in argv or "--no-frontdoor" in argv:
        return main_frontdoor(smoke="--smoke" in argv,
                              frontdoor=frontdoor_on)
    if "--fleet-smoke" in argv:
        if disagg_ab:
            return main_disagg(smoke=True, disagg=disagg_on)
        return main_fleet(smoke=True, prefix_affinity=prefix_affinity)
    if "--fleet" in argv:
        if disagg_ab:
            return main_disagg(smoke="--smoke" in argv, disagg=disagg_on)
        return main_fleet(smoke="--smoke" in argv,
                          prefix_affinity=prefix_affinity)
    if "--chaos-smoke" in argv:
        return main_chaos(smoke=True)
    if "--chaos" in argv:
        return main_chaos(smoke="--smoke" in argv)
    if "--sustained" in argv:
        return main_sustained(smoke="--smoke" in argv)
    if "--serve-smoke" in argv:
        return main_serve(smoke=True, flash_decode=flash_decode,
                          spec_decode=spec,
                          int8_kv=int8_kv, prefix_cache=prefix_cache,
                          host_offload=host_offload,
                          paged_kv=paged_kv)
    if "--serve" in argv:
        return main_serve(flash_decode=flash_decode,
                          spec_decode=spec,
                          int8_kv=int8_kv, prefix_cache=prefix_cache,
                          host_offload=host_offload,
                          paged_kv=paged_kv)
    if "--sweep" in argv:
        return main_sweep()
    if "--xl-compute" in argv:
        return main_xl_compute()
    if "--xl" in argv:
        return main_xl()
    if "--bert-sparse" in argv:
        return main_bert(sparse=True)
    if "--bert" in argv:
        return main_bert()
    return main()


if __name__ == "__main__":
    from deepspeed_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    sys.exit(_dispatch(sys.argv[1:]))
