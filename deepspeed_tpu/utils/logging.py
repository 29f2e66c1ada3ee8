"""Logging utilities.

TPU-native counterpart of the reference's single-logger + rank-filtered logging
(/root/reference/deepspeed/utils/logging.py:37-60). Rank filtering uses
``jax.process_index()`` when JAX is initialized, falling back to env vars so the
logger works before distributed init (mirroring the reference's use of
``torch.distributed.get_rank`` guarded by ``is_initialized``).
"""

import logging
import os
import sys

log_levels = {
    "debug": logging.DEBUG,
    "info": logging.INFO,
    "warning": logging.WARNING,
    "error": logging.ERROR,
    "critical": logging.CRITICAL,
}


def create_logger(name=None, level=logging.INFO):
    """Create a logger with a stdout stream handler (reference logging.py:14-34)."""
    logger_ = logging.getLogger(name)
    logger_.setLevel(level)
    logger_.propagate = False
    if not logger_.handlers:
        formatter = logging.Formatter(
            "[%(asctime)s] [%(levelname)s] [%(name)s] %(message)s")
        # stderr, so programmatic stdout (a benchmark run's result line) stays clean
        handler = logging.StreamHandler(stream=sys.stderr)
        handler.setLevel(level)
        handler.setFormatter(formatter)
        logger_.addHandler(handler)
    return logger_


logger = create_logger(name="DeepSpeedTPU", level=logging.INFO)


def _get_rank():
    # Process index when multi-host JAX is initialized; env fallback otherwise.
    try:
        import jax
        return jax.process_index()
    except Exception:
        return int(os.environ.get("RANK", os.environ.get("JAX_PROCESS_ID", 0)))


def log_dist(message, ranks=None, level=logging.INFO):
    """Log ``message`` only on the listed ranks (-1 or None = all ranks).

    Mirrors reference utils/logging.py:40-60.
    """
    should_log = ranks is None or len(ranks) == 0 or -1 in ranks
    if not should_log:
        should_log = _get_rank() in set(ranks)
    if should_log:
        final_message = "[Rank {}] {}".format(_get_rank(), message)
        logger.log(level, final_message)
