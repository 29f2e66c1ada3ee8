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
