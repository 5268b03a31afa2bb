"""Runtime observability: spans, metrics, trace export — and analytics.

The measurement layer the paper's contribution implies (its result IS a
per-phase runtime table): injectable clocks (``obs.clock`` — the one
sanctioned wall-clock site in ``src/repro``), nested spans with
device-bracketed timing recorded outside jit boundaries (``obs.trace``),
counters/gauges/histograms plus the live-device-memory sampler
(``obs.metrics``), and pluggable exporters — JSONL, Chrome/Perfetto
trace-event JSON, and Prometheus text (``obs.export``,
``obs.telemetry``).

On top of the recording layer: ``obs.timeline`` reconstructs a finished
trace into per-phase critical path, psum overlap, and throughput;
``obs.progress`` publishes live done/total/ETA status for in-flight
jobs; ``obs.telemetry`` serves ``/metrics`` + ``/healthz`` +
``/progress`` over stdlib HTTP.  See obs/README.md for the span and
metric catalog, the viewing instructions, and the "watch a long job"
quickstart.
"""
from .clock import MONOTONIC, Clock, FakeClock, MonotonicClock, now
from .export import (ChromeTraceExporter, JsonlExporter, exporter_names,
                     get_exporter, register_exporter)
from .metrics import (Counter, Gauge, Histogram, MeteredSource,
                      MetricsRegistry, live_device_bytes)
from .progress import ProgressReporter
from .telemetry import PrometheusExporter, TelemetryServer, prometheus_text
from .timeline import PhaseStat, Timeline, TSpan
from .trace import Span, Tracer, current_tracer, deep_tracing, tracing

__all__ = [
    "Clock", "MonotonicClock", "FakeClock", "MONOTONIC", "now",
    "Span", "Tracer", "tracing", "current_tracer", "deep_tracing",
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "live_device_bytes", "MeteredSource",
    "JsonlExporter", "ChromeTraceExporter", "register_exporter",
    "get_exporter", "exporter_names",
    "Timeline", "TSpan", "PhaseStat",
    "ProgressReporter",
    "TelemetryServer", "prometheus_text", "PrometheusExporter",
]
