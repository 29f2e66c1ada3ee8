"""Shared setup for the standalone perf scripts in this directory.

Each script calls ``setup()`` before importing deepspeed_tpu: it puts the
repo root on sys.path (``python tests/perf/x.py`` only gets the script's
own directory, which is also how this module resolves). The platform is
JAX's own choice: the TPU where there is one, the CPU under
``JAX_PLATFORMS=cpu``.
"""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def setup():
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
