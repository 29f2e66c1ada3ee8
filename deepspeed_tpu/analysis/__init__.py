"""graftlint: JAX-contract static analyzer + fleet race detector.

Stdlib-``ast`` only — no new dependencies, safe to import from anywhere
(including conftest and bin/lint.sh). Entry points:

- CLI: ``python -m deepspeed_tpu.analysis [paths] [--baseline F]
  [--format text|json]`` (see ``__main__``).
- Library: ``collect_findings(paths)`` / ``analyze_file(path)``.
- Markers: ``deepspeed_tpu.analysis.annotations.hot_path`` and the
  ``_THREAD_OWNED`` class-attr convention.

Rule catalog and annotation guide: docs/ANALYSIS.md.
"""

from . import annotations
from .core import (AnalysisConfig, Finding, analyze_file, analyze_source,
                   apply_baseline, baseline_key, collect_findings,
                   load_baseline, write_baseline)

DEFAULT_BASELINE = "baseline.json"  # relative to this package directory

__all__ = [
    "AnalysisConfig", "Finding", "analyze_file", "analyze_source",
    "apply_baseline", "baseline_key", "collect_findings", "load_baseline",
    "write_baseline", "annotations", "DEFAULT_BASELINE",
]
