"""deepspeed_tpu.telemetry — unified observability for training + serving.

One dependency-free subsystem every engine emits into:

- ``MetricsRegistry`` (registry.py): counters / gauges /
  bounded-reservoir histograms with windowed snapshots.
- ``SpanRecorder`` (tracing.py): per-request trace spans exported as
  Chrome trace-event JSON (Perfetto-loadable) and a JSONL flight ring.
  ``process_recorder()`` is the process's one: its way to ready
  (``setup/*``, ``compile/*``), whatever an engine's telemetry switch says.
- ``TimeseriesCollector`` (timeseries.py): periodic windowed registry
  snapshots in a bounded ring — the per-window TTFT/ITL/queue-depth
  curves the sustained-load harness (loadgen/) reports, exportable as
  Chrome counter events next to the span export.
- ``RecompileDetector`` / ``install_compile_listeners`` /
  ``startup_summary`` (instrumentation.py): jit cache-miss detection as a
  live gauge; the process's one set of ``jax.monitoring`` listeners, which
  turn every trace, lowering and compile (or cache read) into a named span
  of the process recorder; and what ``engine.metrics()["startup"]`` reads
  from them.
- ``prometheus_text`` / ``PrometheusEndpoint`` /
  ``TensorBoardScalarWriter`` (exporters.py): the read-side. The
  tensorboard extra is imported lazily — this package imports clean on
  a bare interpreter.
- ``TraceContext`` / ``merged_trace`` / ``validate_trace``
  (distributed.py): propagated trace context (shared tid + hop
  counter) and the fleet-wide merge that binds cross-replica hops with
  Perfetto flow arrows.
- ``build_autopsy`` / ``worst_requests`` (autopsy.py): the structured
  "why was this request slow?" answer assembled from the rings.
- ``AlertRule`` / ``AlertManager`` / ``default_rules`` (alerts.py):
  declarative SLO burn-rate alerting over the collector's windows.
- ``ProgramRegistry`` / ``HBMLedger`` / ``cost_model_gate`` (xray.py):
  the compiled-program cost/memory observatory — per-program HLO
  fingerprints, cost_analysis flops/bytes, roofline gauges against
  ``DEVICE_PEAKS``, the predicted-vs-live HBM ledger, and the
  hardware-free cost-model regression gate.

See docs/OBSERVABILITY.md for the full contract.
"""

from deepspeed_tpu.telemetry.alerts import (
    AlertManager,
    AlertRule,
    default_rules,
)
from deepspeed_tpu.telemetry.autopsy import build_autopsy, worst_requests
from deepspeed_tpu.telemetry.distributed import (
    TraceContext,
    TraceError,
    merged_trace,
    validate_trace,
    write_merged_trace,
)

from deepspeed_tpu.telemetry.exporters import (
    PrometheusEndpoint,
    TensorBoardScalarWriter,
    prometheus_digest,
    prometheus_text,
)
from deepspeed_tpu.telemetry.instrumentation import (
    RecompileDetector,
    count_compiles_into,
    install_compile_listeners,
    mark_ready,
    startup_summary,
)
from deepspeed_tpu.telemetry.registry import (
    Counter,
    Gauge,
    Histogram,
    MergedRegistry,
    MetricsRegistry,
    NullRegistry,
)
from deepspeed_tpu.telemetry.timeseries import TimeseriesCollector
from deepspeed_tpu.telemetry.tracing import (
    NullRecorder,
    SpanRecorder,
    process_recorder,
)
from deepspeed_tpu.telemetry.xray import (
    DEVICE_PEAKS,
    HBMLedger,
    ProgramRegistry,
    cost_model_gate,
)

__all__ = [
    "TimeseriesCollector",
    "Counter",
    "Gauge",
    "Histogram",
    "MergedRegistry",
    "MetricsRegistry",
    "NullRegistry",
    "NullRecorder",
    "SpanRecorder",
    "RecompileDetector",
    "count_compiles_into",
    "install_compile_listeners",
    "mark_ready",
    "startup_summary",
    "process_recorder",
    "prometheus_text",
    "prometheus_digest",
    "PrometheusEndpoint",
    "TensorBoardScalarWriter",
    "TraceContext",
    "TraceError",
    "merged_trace",
    "validate_trace",
    "write_merged_trace",
    "build_autopsy",
    "worst_requests",
    "AlertRule",
    "AlertManager",
    "default_rules",
    "ProgramRegistry",
    "HBMLedger",
    "cost_model_gate",
    "DEVICE_PEAKS",
]
