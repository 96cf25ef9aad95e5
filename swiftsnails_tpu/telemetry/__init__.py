"""Unified telemetry: tracing, metrics, audit — and the flight recorder.

Six legs, one subsystem (the observability the reference never had —
SURVEY §5 lists glog lines and a chrono ``Timer`` as its entire surface):

* :mod:`~swiftsnails_tpu.telemetry.tracer` — host-side nestable spans with
  Chrome trace-event export, bridged to ``jax.profiler`` step annotations;
* :mod:`~swiftsnails_tpu.telemetry.registry` — named counters / gauges /
  histograms flushed through pluggable sinks
  (:class:`~swiftsnails_tpu.utils.metrics.MetricsLogger` is the JSONL sink;
  :class:`StdoutSummarySink` the terminal one);
* :mod:`~swiftsnails_tpu.telemetry.audit` — per-collective op counts/bytes
  and cost/memory analysis from a step function's optimized HLO, sync and
  async collective forms alike;
* :mod:`~swiftsnails_tpu.telemetry.ledger` — durable append-only JSONL run
  ledger (atomic tmp+rename writes): bench results, training runs, outage
  events, black-box dumps — a record, never replayed as a result;
* :mod:`~swiftsnails_tpu.telemetry.goodput` — MFU, step-time decomposition
  (compute vs collective vs host-blocked), words/sec-vs-roofline, combining
  tracer spans with the HLO audit's cost analysis;
* :mod:`~swiftsnails_tpu.telemetry.blackbox` — bounded ring of the last N
  steps' spans/metrics, dumped to disk on exception, NaN/Inf loss, SIGTERM.

The serving/freshness plane adds three more (docs/OBSERVABILITY.md):

* :mod:`~swiftsnails_tpu.telemetry.request_trace` — request-scoped
  distributed tracing: propagable trace/span ids, deterministic head
  sampling plus always-keep tail sampling for anomalies, ring-buffered
  with JSONL / Chrome-trace export;
* :mod:`~swiftsnails_tpu.telemetry.slo` — windowed SLO tracker with
  multi-window burn-rate alerting, error-budget accounting, and a
  ``should_scale()`` hook, emitting ``slo_burn`` ledger events;
* :mod:`~swiftsnails_tpu.telemetry.ops` — the one-screen fleet dashboard
  (``python -m swiftsnails_tpu ops`` / the serve REPL's ``ops`` op).

And the training plane three more (docs/OBSERVABILITY.md §11–13):

* :mod:`~swiftsnails_tpu.telemetry.timeseries` — continuous profiling: a
  bounded ring of periodic registry/goodput samples, JSONL export, and
  terminal sparklines for ``ledger-report`` / ``ops``;
* :mod:`~swiftsnails_tpu.telemetry.drift` — the online drift sentinel:
  EWMA/CUSUM detectors over the training-plane signals, transition-edged
  ``drift`` ledger events, and atomic incident bundles;
* ``ledger-report --diff A B`` (:func:`goodput.throughput_attribution`) —
  regression attribution between two run/bench records.

Off by default: the TrainLoop only constructs these when the ``telemetry``
or ``trace_path`` config keys are set, and its hot path pays one
enabled-flag check otherwise.
"""

from swiftsnails_tpu.telemetry.audit import (
    audit_compiled,
    audit_step,
    collective_bytes,
    collective_stats,
    compiled_collective_bytes,
)
from swiftsnails_tpu.telemetry.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricRegistry,
    StdoutSummarySink,
)
from swiftsnails_tpu.telemetry.blackbox import BlackBox
from swiftsnails_tpu.telemetry.drift import (
    DriftSentinel,
    EwmaCusum,
    build_incident_bundle,
    bundle_complete,
)
from swiftsnails_tpu.telemetry.goodput import (
    goodput_report,
    peaks_for,
    step_time_decomposition,
    throughput_attribution,
)
from swiftsnails_tpu.telemetry.ledger import (
    Ledger,
    config_hash,
    env_fingerprint,
)
from swiftsnails_tpu.telemetry.ops import render_ops, render_ops_from_ledger
from swiftsnails_tpu.telemetry.request_trace import (
    RequestContext,
    RequestTracer,
    tree_complete,
)
from swiftsnails_tpu.telemetry.slo import SloObjective, SloTracker
from swiftsnails_tpu.telemetry.summary import summarize_file
from swiftsnails_tpu.telemetry.timeseries import (
    TimeSeriesStore,
    render_sparklines,
    sparkline,
)
from swiftsnails_tpu.telemetry.tracer import Tracer

# the JSONL sink IS the existing MetricsLogger (same ``log``/``close``
# surface) — imported under the sink name so call sites read as intended
from swiftsnails_tpu.utils.metrics import MetricsLogger as JsonlSink

__all__ = [
    "Tracer",
    "RequestContext",
    "RequestTracer",
    "SloObjective",
    "SloTracker",
    "tree_complete",
    "render_ops",
    "render_ops_from_ledger",
    "MetricRegistry",
    "Counter",
    "Gauge",
    "Histogram",
    "JsonlSink",
    "StdoutSummarySink",
    "BlackBox",
    "Ledger",
    "TimeSeriesStore",
    "DriftSentinel",
    "EwmaCusum",
    "build_incident_bundle",
    "bundle_complete",
    "render_sparklines",
    "sparkline",
    "throughput_attribution",
    "audit_compiled",
    "audit_step",
    "collective_bytes",
    "collective_stats",
    "compiled_collective_bytes",
    "config_hash",
    "env_fingerprint",
    "goodput_report",
    "peaks_for",
    "step_time_decomposition",
    "summarize_file",
]
