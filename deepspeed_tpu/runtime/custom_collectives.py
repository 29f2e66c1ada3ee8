"""Compressed collectives for 1-bit Adam.

The reference implements an error-compensated 1-bit allreduce with raw MPI +
cupy (deepspeed/runtime/custom_collectives.py:10-155: my_igather/gather/
allgather of sign-packed bits) because NCCL lacked non-blocking gathers. On
TPU the same exchange maps onto two XLA collectives over the data-parallel
mesh axis: an ``all_to_all`` (each worker scatters its sign-packed chunks —
the reference's igather phase 1) and an ``all_gather`` (the server-side
re-broadcast — phase 2), both riding ICI. Signs are genuinely bit-packed into
uint8 words, so the wire volume is n/8 bytes + one fp32 scale per phase —
the same 1-bit-per-element compression the reference achieves with
cupy.packbits (onebit_adam.py:98-102).

Everything here is pure-functional and shard_map-compatible; use inside
``shard_map(..., mesh, in_specs=..., check_vma=False)`` over the 'data' axis.
"""

import jax
import jax.numpy as jnp
import numpy as np


def pack_signs(x):
    """Pack the sign bits of ``x`` (>=0 → 1, <0 → 0) into uint8 words.

    x: [n] float, n % 8 == 0 → uint8 [n/8]. Big-endian within each byte,
    matching numpy/cupy packbits so tests can cross-check against numpy.
    """
    bits = (x >= 0).astype(jnp.uint8).reshape(-1, 8)
    weights = jnp.asarray([128, 64, 32, 16, 8, 4, 2, 1], dtype=jnp.uint8)
    return jnp.sum(bits * weights[None, :], axis=1, dtype=jnp.uint8)


def unpack_signs(packed):
    """uint8 [m] → float32 [m*8] of ±1 values."""
    shifts = jnp.asarray([7, 6, 5, 4, 3, 2, 1, 0], dtype=jnp.uint8)
    bits = (packed[:, None] >> shifts[None, :]) & 1
    return (bits.astype(jnp.float32) * 2.0 - 1.0).reshape(-1)


def corrected_size(n, world_size):
    """Padded element count: the invariant is n % world_size == 0 and
    (n // world_size) % 8 == 0, i.e. n a multiple of 8*world_size.

    The reference rounds up to world_size*lcm(world_size,8)
    (onebit_adam.py:86, :295-299) — up to world_size/gcd(world_size,8)×
    over-padding, which biases the quantization scale (norm/sqrt(n) over the
    zero padding) low for small tensors at large world sizes. We pad to the
    minimal sufficient block instead.
    """
    block = world_size * 8
    if n % block:
        n += block - (n % block)
    return n


def compressed_allreduce(buffer, worker_error, server_error, axis_name):
    """Error-compensated 1-bit allreduce (reference Compressed_Allreduce,
    onebit_adam.py:104-233), as a pure function over a mesh axis.

    Args:
      buffer: [n] float32, this worker's value (n already padded to
        ``corrected_size``; the optimizer pads).
      worker_error: [n] float32 error-feedback state (phase 1).
      server_error: [n / W] float32 error-feedback state for this worker's
        server chunk (phase 2).
      axis_name: mesh axis to reduce over.

    Returns (averaged [n], new_worker_error, new_server_error). The result is
    identical on every worker (it is built from all-gathered server chunks).
    """
    w = jax.lax.psum(1, axis_name)
    n = buffer.shape[0]
    chunk = n // w

    # --- worker-side compression (with error feedback)
    buffer = buffer + worker_error
    worker_scale = jnp.linalg.norm(buffer) / np.sqrt(n)
    sign = jnp.where(buffer >= 0, 1.0, -1.0)
    new_worker_error = buffer - worker_scale * sign

    # --- phase 1: scatter sign chunks so worker r holds everyone's chunk r
    packed = pack_signs(sign).reshape(w, chunk // 8)
    recv_signs, all_scales = gather_tpu(axis_name, packed, worker_scale)

    # --- server-side average + re-compression for my chunk
    unpacked = jax.vmap(unpack_signs)(recv_signs)                 # [w, chunk]
    server_m = jnp.mean(unpacked * all_scales[:, None], axis=0)
    server_m = server_m + server_error
    server_scale = jnp.linalg.norm(server_m) / np.sqrt(chunk)
    server_sign = jnp.where(server_m >= 0, 1.0, -1.0)
    new_server_error = server_m - server_scale * server_sign

    # --- phase 2: all_gather compressed server chunks
    server_packed = pack_signs(server_sign)                       # [chunk/8]
    gathered, gathered_scales = allgather_tpu(axis_name, server_packed,
                                              server_scale)
    out = (jax.vmap(unpack_signs)(gathered) *
           gathered_scales[:, None]).reshape(-1)
    return out, new_worker_error, new_server_error


def quantize_error_feedback(buffer, error):
    """Single-party 1-bit quantization with error feedback — the degenerate
    (identical-workers) form of compressed_allreduce.

    Under single-controller GSPMD the gradients reaching the optimizer are
    already globally averaged, so every worker's momentum is identical and
    phase 1 of the exchange is mathematically the identity; what remains is
    the server-side quantize/compensate. Used by OnebitAdam's jit path; the
    full two-phase collective above is for shard_map pipelines that keep
    per-worker local gradients.
    """
    compensated = buffer + error
    scale = jnp.linalg.norm(compensated) / np.sqrt(compensated.size)
    sign = jnp.where(compensated >= 0, 1.0, -1.0)
    new_error = compensated - scale * sign
    return scale * sign, new_error


# Reference-compatible collective phases (custom_collectives.py:10-155:
# gather_cuda/gather_host scatter packed sign chunks + scales so rank r
# holds everyone's chunk r; allgather_cuda/allgather_host rebroadcast the
# re-compressed server chunks). The reference needs four variants because
# raw-MPI igather requires host buffers while cupy sometimes allows device
# pointers; on TPU ONE implementation per phase serves both — an XLA
# collective over the mesh axis, usable inside shard_map — and they are
# the actual building blocks of compressed_allreduce above.

def gather_tpu(axis_name, sign_list_packed, worker_scale):
    """Phase-1 exchange: each worker offers [w, chunk/8] packed sign
    chunks; returns (this worker's received [w, chunk/8] — chunk r from
    every peer — and everyone's scales [w])."""
    recv_signs = jax.lax.all_to_all(sign_list_packed, axis_name,
                                    split_axis=0, concat_axis=0,
                                    tiled=False)
    all_scales = jax.lax.all_gather(worker_scale, axis_name)
    return recv_signs, all_scales


def allgather_tpu(axis_name, server_sign_packed, server_scale):
    """Phase-2 exchange: rebroadcast each worker's re-compressed server
    chunk [chunk/8] + scale; returns ([w, chunk/8], [w])."""
    gathered = jax.lax.all_gather(server_sign_packed, axis_name)
    gathered_scales = jax.lax.all_gather(server_scale, axis_name)
    return gathered, gathered_scales


gather_cuda = gather_host = gather_tpu
allgather_cuda = allgather_host = allgather_tpu
