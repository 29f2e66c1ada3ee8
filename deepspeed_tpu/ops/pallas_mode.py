"""The one place that decides how a Pallas kernel is launched.

On a ``tpu`` backend every kernel is compiled by Mosaic, and a kernel the
compiler refuses raises — nothing gives way to interpret mode or to a
``jnp`` reference behind the caller's back. Everywhere else (the CPU test
mesh) the same kernel bodies run through the Pallas interpreter.
"""

import functools

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


def shared_launch(*static_argnames):
    """Decorator for a kernel's launcher: call sites with the same operand
    shapes and the same static arguments share ONE trace and ONE lowering.

    A model's layers are unrolled, and JAX traces a Pallas kernel's body
    anew at every ``pallas_call`` site (no cache in 0.9.0), forward and
    backward, on every run before the compile cache is asked: for a kernel
    whose body is long that is seconds of a training cell's set-up. The
    launcher becomes a ``jax.jit`` whose key holds the static arguments and
    how ``kernel_call`` will launch (``interpret()``, decided here and
    nowhere else). Everything else the trace depends on must be an operand
    or a static argument: the launcher reads no environment variable and no
    module global that may change."""
    def decorate(fn):
        def keyed(*args, _launch_mode, **kwargs):
            del _launch_mode
            return fn(*args, **kwargs)

        # Its name, not ``wraps``: jit checks the static names against the
        # signature, which ``__wrapped__`` would hand it the launcher's.
        keyed.__name__ = keyed.__qualname__ = fn.__name__
        jitted = jax.jit(keyed,
                         static_argnames=static_argnames + ("_launch_mode",))

        @functools.wraps(fn)
        def launch(*args, **kwargs):
            return jitted(*args, _launch_mode=interpret(), **kwargs)
        return launch
    return decorate
