"""JAX's persistent compilation cache, at a place chosen from outside.

The cache key includes the directory, so a directory that moves never
hits: the path is either the one ``JAX_COMPILATION_CACHE_DIR`` names (JAX
reads that variable itself, so nothing is set here) or one fixed path
inside the checkout — never one built from a temporary name, a process id
or the time. ``chip_smoke.py`` and the benchmark's drivers call
``place_compile_cache()`` before their first compile; ``tests/conftest.py``
keeps the cache off.
"""

import os

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache (listed in .gitignore).
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def place_compile_cache():
    """Point JAX's persistent compilation cache at its directory and
    return that directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set the
    config is left alone."""
    from_env = os.environ.get(CACHE_DIR_ENV)
    if from_env:
        return from_env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
