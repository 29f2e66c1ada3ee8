"""Re-sweep the kernel tile autotuner on the GPT-2 shapes and refresh the
bundled table.

The bundled table (`deepspeed_tpu/ops/autotune_table.json`) was swept
with the split two-kernel backward; the fused one-pass backward changes
the cost surface (no kv-innermost grid in the backward), so the winning
tiles may shift — each flash-attention sweep candidate is timed through
a full fwd+bwd step (see attention._autotuned_blocks' make_run), so a
re-run under this script refreshes the table against whichever backward
mode ('fused'/'split', printed per shape) the current kernels pick. The
script also sweeps the flash-DECODE kernel
(ops/transformer/kernels/decode_attention.py) at the serving shapes, so
the inference engine's traced calls — which consult tables only — pick
up tuned kv tiles.

Runs the online sweeps eagerly (the autotuner only sweeps outside a
trace), then copies the winners from the user cache into the bundled
table, schema-validating the result before writing.

Usage: python tests/perf/autotune_sweep.py
           [--shapes b8t1024,b4t2048,...]
           [--decode-shapes b16t1024,b1s32t1024,...]
           [--decode-q8-shapes b16t1024,b16s5t1024,...]
       (decode specs are bB[sS]tT; s>1 sweeps the chunked-prefill
       append-attention shapes; the q8 list sweeps the int8-KV kernel
       family "decode_attention_q8" at the same grammar.)
"""

import argparse
import json
import os
import sys

import _platform

_platform.setup()

# "force": re-sweep even for shapes already in the bundled table — that
# table predates the fused backward.
os.environ["DS_TPU_AUTOTUNE"] = "force"

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.ops import autotuner
from deepspeed_tpu.ops.transformer.kernels.attention import (
    _bwd_mode, flash_attention, flash_signature)
from deepspeed_tpu.ops.transformer.kernels.decode_attention import (
    decode_signature, flash_decode_attention, flash_decode_attention_q8,
    quantize_kv)

# (batch, seq) grid; heads/dim are GPT-2
# medium's (the autotune signature keys on the full shape).
DEFAULT_SHAPES = "b8t1024,b12t1024,b16t1024,b4t2048,b8t2048,b2t4096,b4t4096"

# (slots[, q_len], cache plane len) decode grid — the GPT-2 serving
# cells run 16 slots at a 1024-position pool; the longer planes cover larger
# serving configs. No sNN means s=1 (the decode scan's query shape);
# the b1sNN entries are the chunked-prefill APPEND shapes — the engine's
# mixed step appends a [1, prefill_chunk] prompt slice through the same
# kernel, so its q_len>1 signature needs its own tuned kv tile. The
# bNNs5 entries are the SPECULATIVE VERIFY shapes: with spec_decode on,
# every decode step scores spec_k+1 query rows per slot (default
# spec_k=4 -> s=5) through the same kernel, so the speculation lane's
# signature gets its own tuned tile too.
DEFAULT_DECODE_SHAPES = ("b16t1024,b16t2048,b8t2048,b8t4096,"
                         "b1s32t1024,b1s32t2048,b1s64t2048,"
                         "b16s5t1024,b16s5t2048,b8s5t2048")

# int8-KV ("decode_attention_q8") grid — same grammar, the serving and
# speculative-verify shapes the engine dispatches with int8_kv on. The
# q8 kernel streams HALF the cache bytes per kv tile (int8 codes + a
# thin fp32 scale row), so its winning tile need not match the fp one —
# it gets its own family and its own swept entries.
DEFAULT_DECODE_Q8_SHAPES = ("b16t1024,b16t2048,b8t2048,b8t4096,"
                            "b1s32t1024,b16s5t1024,b16s5t2048")


def _parse_decode_spec(spec):
    # Spec grammar: bB[sS]tT — s defaults to 1 (pure decode); s>1 is a
    # chunked-prefill append slice (or the spec_k+1 verify width).
    body, t = spec[1:].split("t")
    b, s = (int(x) for x in body.split("s")) if "s" in body \
        else (int(body), 1)
    return b, s, int(t)


def sweep_flash(args, swept_keys):
    rng = np.random.RandomState(0)
    for spec in args.shapes.split(","):
        spec = spec.strip()
        if not spec:
            continue
        b, t = (int(x) for x in spec[1:].split("t"))
        q, k, v = (jnp.asarray(rng.randn(b, args.heads, t, args.dim),
                               jnp.bfloat16) for _ in range(3))
        # Eager call -> autotuner sweeps candidates and records the winner.
        out = flash_attention(q, k, v, causal=True)
        out.block_until_ready()
        # The key the autotuner recorded for this shape — built with the
        # exported formatters so the key cannot drift from attention.py.
        swept_keys.append(autotuner.table_key(
            "flash_attention",
            flash_signature(b, args.heads, t, t, args.dim,
                            jnp.bfloat16, causal=True)))
        print("swept", spec, "(backward mode: {})".format(
            _bwd_mode(t, args.dim, jnp.bfloat16)), flush=True)


def sweep_decode(args, swept_keys):
    rng = np.random.RandomState(1)
    for spec in args.decode_shapes.split(","):
        spec = spec.strip()
        if not spec:
            continue
        b, s, t = _parse_decode_spec(spec)
        q = jnp.asarray(rng.randn(b, args.heads, s, args.dim), jnp.bfloat16)
        k = jnp.asarray(rng.randn(b, args.heads, t, args.dim), jnp.bfloat16)
        v = jnp.asarray(rng.randn(b, args.heads, t, args.dim), jnp.bfloat16)
        # Worst-case frontier (every kv block active; the append's S new
        # rows still fit the plane) — the sweep inside
        # resolve_decode_block times the same frontier, so the tuned
        # tile is the end-of-generation one.
        pos = jnp.full((b,), t - s, jnp.int32)
        out = flash_decode_attention(q, k, v, pos)
        out.block_until_ready()
        swept_keys.append(autotuner.table_key(
            "decode_attention",
            decode_signature(b, args.heads, s, t, args.dim, jnp.bfloat16)))
        print("swept decode", spec, flush=True)


def sweep_decode_q8(args, swept_keys):
    rng = np.random.RandomState(2)
    for spec in args.decode_q8_shapes.split(","):
        spec = spec.strip()
        if not spec:
            continue
        b, s, t = _parse_decode_spec(spec)
        q = jnp.asarray(rng.randn(b, args.heads, s, args.dim), jnp.bfloat16)
        # Quantized planes, the exact operand layout the engine holds:
        # int8 codes + per-(head, position) fp32 scales.
        kq, ks = quantize_kv(jnp.asarray(
            rng.randn(b, args.heads, t, args.dim), jnp.bfloat16))
        vq, vs = quantize_kv(jnp.asarray(
            rng.randn(b, args.heads, t, args.dim), jnp.bfloat16))
        pos = jnp.full((b,), t - s, jnp.int32)
        out = flash_decode_attention_q8(q, kq, vq, ks, vs, pos)
        out.block_until_ready()
        # The q8 family keys on the QUERY dtype (the codes are always
        # int8) — same convention as resolve_decode_block.
        swept_keys.append(autotuner.table_key(
            "decode_attention_q8",
            decode_signature(b, args.heads, s, t, args.dim, jnp.bfloat16)))
        print("swept decode q8", spec, flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default=DEFAULT_SHAPES)
    ap.add_argument("--decode-shapes", default=DEFAULT_DECODE_SHAPES)
    ap.add_argument("--decode-q8-shapes", default=DEFAULT_DECODE_Q8_SHAPES)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--dim", type=int, default=64)
    args = ap.parse_args()

    swept_keys = []
    sweep_flash(args, swept_keys)
    sweep_decode(args, swept_keys)
    sweep_decode_q8(args, swept_keys)

    user_path = autotuner._user_cache_path()
    try:
        with open(user_path) as f:
            user = json.load(f)
    except (OSError, ValueError):
        user = {}
    # Promote ONLY this run's winners: the user cache also holds entries
    # from sweeps predating the current kernels (the staleness this
    # script exists to purge) and unrelated shapes.
    fresh = {k: user[k] for k in swept_keys if k in user}
    if not fresh:
        print("no swept entries in the user cache (off-TPU run sweeps "
              "nothing); bundled table left unchanged", flush=True)
        return 0
    bundled_path = autotuner._BUNDLED_PATH
    try:
        with open(bundled_path) as f:
            bundled = json.load(f)
    except (OSError, ValueError):
        bundled = {}
    changed = 0
    for key, entry in fresh.items():
        if bundled.get(key, {}).get("choice") != entry["choice"]:
            changed += 1
        bundled[key] = entry
    # A malformed merge must die here, not at serving-time dispatch.
    autotuner.validate_table(bundled, source=bundled_path)
    with open(bundled_path, "w") as f:
        json.dump(bundled, f, indent=1, sort_keys=True)
        f.write("\n")
    print("bundled table updated: {}/{} swept entries changed -> {}".format(
        changed, len(fresh), bundled_path), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
