"""paddle_tpu.observability — unified observability layer (ISSUE r9 + r10).

One registry, seven capabilities:

  * metrics registry (registry.py): Counter/Gauge/Histogram with labels,
    thread-safe, near-zero overhead while FLAGS_metrics is off;
  * sinks (sinks.py): append-only JSONL event log + atomic Prometheus
    textfile exporter under FLAGS_metrics_dir;
  * per-step telemetry (telemetry.py): the runtime emits loss / grad-norm /
    lr / throughput / MFU / per-phase times from inside jit.TrainStep and
    resilience.ResilientTrainer;
  * span tracing (spans.py) + crash flight recorder (flight_recorder.py):
    one span ring shared by the profiler, the chrome-trace merge, and the
    atomic crash dumps triggered by the NaN guard / preemption / uncaught
    exceptions / anomalies;
  * cluster aggregation (cluster.py): each rank publishes its step record
    through the process-group store; rank 0 aggregates min/median/max/p95
    per phase and flags stragglers (ClusterTelemetry(k=, m=));
  * anomaly engine (anomaly.py): rolling-window detectors (loss/grad-norm
    spike, step-time regression, throughput collapse, compile-cache
    collapse) that dump the flight recorder on detection (FLAGS_anomaly);
  * memory accounting (memory.py) + HTTP endpoint (serve.py): per-device
    HBM gauges, per-executable XLA cost/memory analysis, and /metrics +
    /healthz on FLAGS_metrics_port.

Importing this package registers FLAGS_metrics, FLAGS_metrics_dir,
FLAGS_flight_recorder_steps, FLAGS_anomaly and FLAGS_metrics_port.
"""
from . import (anomaly, cluster, flight_recorder, memory,  # noqa: F401
               registry, serve, sinks, spans, telemetry)
from .anomaly import AnomalyEngine, anomaly_enabled  # noqa: F401
from .cluster import ClusterTelemetry  # noqa: F401
from .flight_recorder import FlightRecorder, get_flight_recorder  # noqa: F401
from .memory import (device_memory_stats, memory_report,  # noqa: F401
                     note_executable, update_memory_gauges)
from .registry import (REGISTRY, Counter, Gauge, Histogram,  # noqa: F401
                       MetricsRegistry, counter, default_registry, gauge,
                       histogram, metrics_enabled)
from .serve import MetricsServer, start_metrics_server  # noqa: F401
from .sinks import (JsonlEventLog, parse_prometheus_text,  # noqa: F401
                    prometheus_text, write_prometheus_textfile)
from .spans import record_span, span  # noqa: F401
from .telemetry import StepTelemetry, get_telemetry  # noqa: F401

__all__ = [
    "REGISTRY", "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "counter", "gauge", "histogram", "default_registry", "metrics_enabled",
    "JsonlEventLog", "prometheus_text", "write_prometheus_textfile",
    "parse_prometheus_text", "span", "record_span", "StepTelemetry",
    "get_telemetry", "FlightRecorder", "get_flight_recorder", "reset_all",
    "ClusterTelemetry", "AnomalyEngine", "anomaly_enabled", "MetricsServer",
    "start_metrics_server", "device_memory_stats", "update_memory_gauges",
    "note_executable", "memory_report",
]


def reset_all() -> None:
    """Zero metrics, clear spans, stop the HTTP server, and drop the
    telemetry/flight singletons — test isolation helper."""
    registry.REGISTRY.reset()
    spans.clear()
    telemetry.reset()
    flight_recorder.reset()
    serve.reset()
