"""The one place that decides how a Pallas kernel is launched.

On a ``tpu`` backend every kernel is compiled by Mosaic, and a kernel the
compiler refuses raises — nothing gives way to interpret mode or to a
``jnp`` reference behind the caller's back. Everywhere else (the CPU test
mesh) the same kernel bodies run through the Pallas interpreter.
"""

import jax


def interpret():
    """Value for ``pallas_call(interpret=...)``: True off the TPU."""
    return jax.default_backend() != "tpu"


def kernel_call(name, kernel, **kwargs):
    """``pallas_call`` under a STABLE NAME: ``name`` is the kernel's name in
    every place a reader looks for it. Mosaic's custom call is named after
    it (the profiler's trace prints ``%<name>.<n> = ... custom-call``,
    whatever ``shard_map`` / ``scan`` / ``cond`` the call sits in), and the
    ``jax.named_scope`` of the same name puts it into the ``op_name`` of
    every operation the interpreter makes of the kernel off the TPU, so a
    CPU trace finds the kernel's work under the same word."""
    from jax.experimental import pallas as pl

    call = pl.pallas_call(kernel, name=name, interpret=interpret(), **kwargs)

    def launch(*args):
        with jax.named_scope(name):
            return call(*args)
    return launch
