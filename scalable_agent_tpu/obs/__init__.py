"""Observability subsystem: tracing, metrics, stall + failure forensics.

The runtime instruments itself against a handful of process-global
singletons — ``get_tracer()`` (obs/trace.py, Chrome-trace spans,
disabled by default and near-free when disabled), ``get_registry()``
(obs/registry.py, counters/gauges/histograms, always live),
``get_flight_recorder()`` (obs/flightrec.py, always-on ring buffer of
the last ~64k runtime events, dumped with all-thread stacks on
signal/exception/watchdog), and ``get_watchdog()`` (obs/watchdog.py,
heartbeat registry + stale-thread monitor, disabled by default).
Exporters (obs/exporters.py) turn the registry into Prometheus text —
snapshot file or live HTTP endpoint — and feed the JSONL/TensorBoard
metrics sink; the stall attributor (obs/stall.py) turns per-interval
timings into a named pipeline-bottleneck verdict (including the
watchdog's ``stalled_thread``); obs/aggregate.py merges a multi-process
run's traces and metric snapshots into one fleet view.

See docs/observability.md for the metric-name schema and workflows.
"""

from scalable_agent_tpu.obs.exporters import (
    MetricsHTTPServer,
    MetricsWriter,
    PrometheusExporter,
    render_prometheus,
)
from scalable_agent_tpu.obs.device_telemetry import (
    DeviceTelemetry,
    TelemetryPublisher,
)
from scalable_agent_tpu.obs.health import (
    DetectorSpec,
    HealthMonitor,
    default_detectors,
    read_anomalies,
)
from scalable_agent_tpu.obs.flightrec import (
    FlightRecorder,
    configure_flight_recorder,
    get_flight_recorder,
    install_crash_handlers,
)
from scalable_agent_tpu.obs.ledger import (
    PipelineLedger,
    configure_ledger,
    get_ledger,
)
from scalable_agent_tpu.obs.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
)
from scalable_agent_tpu.obs.stall import CATEGORIES, StallAttributor
from scalable_agent_tpu.obs.trace import (
    Tracer,
    configure_tracer,
    get_tracer,
    load_trace_events,
    span,
    start_host_watch,
    stop_host_watch,
)
from scalable_agent_tpu.obs.watchdog import (
    Watchdog,
    configure_watchdog,
    get_watchdog,
)

__all__ = [
    "CATEGORIES",
    "Counter",
    "DetectorSpec",
    "DeviceTelemetry",
    "FlightRecorder",
    "Gauge",
    "HealthMonitor",
    "Histogram",
    "MetricsHTTPServer",
    "MetricsRegistry",
    "MetricsWriter",
    "PipelineLedger",
    "PrometheusExporter",
    "StallAttributor",
    "TelemetryPublisher",
    "Tracer",
    "Watchdog",
    "configure_flight_recorder",
    "configure_ledger",
    "configure_tracer",
    "configure_watchdog",
    "default_detectors",
    "get_flight_recorder",
    "get_ledger",
    "get_registry",
    "get_tracer",
    "get_watchdog",
    "install_crash_handlers",
    "load_trace_events",
    "read_anomalies",
    "render_prometheus",
    "span",
    "start_host_watch",
    "stop_host_watch",
]
