"""deepspeed_tpu.loadgen — sustained-load harness over the serving engine.

The telemetry package (PR 5) made the engine observable; this package
asks it the questions that matter under LOAD:

- ``WorkloadSpec`` (workload.py): seeded, fully deterministic request
  streams — Poisson/burst/ramp arrivals, heavy-tail lognormal/Zipf
  prompt+output length mixes, JSONL trace replay.
- ``SustainedRunner`` (runner.py): open-loop driver — submits on the
  workload's schedule regardless of backlog, records QueueFull sheds as
  signal, ticks a ``TimeseriesCollector`` into per-window curves. Chaos
  mode (``chaos_plan``/``chaos_after_s``) arms a fault plan mid-run and
  the report grows a ``chaos`` section — recovery time, requests lost,
  SLO attainment during vs outside recovery (docs/RESILIENCE.md).
- ``SLO`` / ``evaluate`` (slo.py): TTFT/ITL budgets, attainment, and
  goodput (tokens from SLO-meeting requests per second per chip).
- ``build_report`` / ``saturation_sweep`` / ``regression_gate``
  (report.py): the JSON report artifact, the stepped-rate capacity
  sweep, and the noise-aware A/B gate whose thresholds come from each
  run's own per-window variance.

``tests/unit/test_loadgen.py`` wires the whole stack end to end on a tiny
CPU engine, and again with one injected fatal step fault, asserting the
recovery invariant. Tests are this package's only callers: the repo's
benchmark (``benchmark/``) drives the engines with its own drivers.
"""

from deepspeed_tpu.loadgen.report import (
    GATE_DEFAULT_METRICS,
    SCHEMA_VERSION,
    build_report,
    regression_gate,
    saturation_sweep,
)
from deepspeed_tpu.loadgen.runner import RunResult, SustainedRunner
from deepspeed_tpu.loadgen.slo import SLO, evaluate
from deepspeed_tpu.loadgen.workload import (
    LoadRequest,
    MixedWorkload,
    WorkloadSpec,
    replay_trace,
    save_trace,
)
from deepspeed_tpu.telemetry import TimeseriesCollector

__all__ = [
    "LoadRequest",
    "MixedWorkload",
    "WorkloadSpec",
    "replay_trace",
    "save_trace",
    "SustainedRunner",
    "RunResult",
    "SLO",
    "evaluate",
    "TimeseriesCollector",
    "SCHEMA_VERSION",
    "GATE_DEFAULT_METRICS",
    "build_report",
    "saturation_sweep",
    "regression_gate",
]
