"""apex_tpu.monitor — structured run telemetry.

The run-health spine the reference never had: its observability ships as
three disconnected pieces (pyprof's nvtx->parse->prof device-time
pipeline, Megatron-style ``Timers``, ad-hoc ``print_rank_last`` loss
lines).  This package gives drivers, amp, the pipeline schedules and
the serving engine ONE structured emission path, in three layers:

1. **Events + sinks** (:mod:`.events`) — a frozen :class:`Event` record
   (``time``, ``step``, ``kind``, ``name``, ``value``, ``attrs``) with
   pluggable sinks: :class:`JsonlSink` (append-only, one valid JSON line
   per event, crash-safe by construction), :class:`MemorySink` (tests),
   :class:`TeeSink`, plus adapters bridging the ``add_scalar`` world in
   both directions (:class:`ScalarWriter` lets ``Timers.write`` target a
   sink unchanged; :class:`WriterSink` forwards events to any
   TensorBoard-like writer).

2. **StepMonitor** (:mod:`.step_monitor`) — per-step recorder computing
   run-health metrics host-side (loss, grad-norm, lr, amp loss-scale /
   overflow via :func:`apex_tpu.amp.scaler.update_telemetry`, tokens/s,
   step wall ms, MFU against :func:`apex_tpu.pyprof.prof.device_spec`)
   with a :class:`Watchdog` (:mod:`.watchdog`) raising once-per-episode
   alarms on non-finite loss, overflow streaks, and wall-clock stalls
   (heartbeat thread; optional ``jax.profiler`` dump of a wedged step).

3. **Summary** (:mod:`.summary`) — parse a JSONL run back into a
   throughput / overflow / phase-time / alarm digest
   (``tools/monitor_summary.py`` is the CLI).

4. **Tracing** (:mod:`.tracing`) — the host side of the wall clock:
   :class:`SpanTracer` spans (Chrome-trace/Perfetto export),
   :class:`StepWaterfall` per-step wall attribution
   (``wall_ms = data_load + dispatch + device_compute +
   telemetry_drain + ckpt_io + other``, ``wall_device_ratio``),
   :class:`DeviceMetricsBuffer`/:class:`DeferredTelemetry` sync-free
   deferred metrics (zero per-step host transfers), and
   :class:`CaptureTrigger` on-demand profiling windows.

5. **Export** (:mod:`.export`) — the live half (ISSUE-17): an
   OpenMetrics :class:`MetricsRegistry` rendered in Prometheus text
   exposition format, the lock-free :class:`MetricsExporter`
   publish/scrape hand-off, the :class:`MetricsServer`
   (``/metrics`` + ``/healthz`` + ``/varz`` on a stdlib daemon
   thread), the :class:`FleetAggregator` trend rings, and
   :func:`registry_from_serve_events` proving the JSONL stays the
   complete source of truth.

When to reach for what: ``monitor`` = run health over time; ``pyprof`` =
where device time went; ``Timers`` = phase wall times (and they export
into the monitor log via ``Timers.events``).  Full story with the JSONL
schema: docs/api/observability.md.
"""
from .export import (
    FleetAggregator,
    MetricsExporter,
    MetricsRegistry,
    MetricsServer,
    PublishedState,
    registry_from_serve_events,
)
from .events import (
    KINDS,
    SCHEMA_VERSION,
    Event,
    JsonlSink,
    MemorySink,
    ScalarWriter,
    Sink,
    TeeSink,
    WriterSink,
    emit_resilience,
)
from .step_monitor import StepMonitor
from .summary import load_events, render, summarize
from .tracing import (
    CaptureTrigger,
    DeferredTelemetry,
    DeviceMetricsBuffer,
    SpanTracer,
    StepWaterfall,
    TraceSession,
    chrome_trace_from_events,
    get_tracer,
    set_tracer,
    span,
    write_chrome_trace,
)
from .watchdog import Watchdog

__all__ = [
    "Event", "Sink", "JsonlSink", "MemorySink", "TeeSink",
    "WriterSink", "ScalarWriter", "emit_resilience",
    "KINDS", "SCHEMA_VERSION",
    "StepMonitor", "Watchdog",
    "load_events", "summarize", "render",
    "SpanTracer", "get_tracer", "set_tracer", "span",
    "StepWaterfall", "TraceSession", "CaptureTrigger",
    "DeviceMetricsBuffer", "DeferredTelemetry",
    "chrome_trace_from_events", "write_chrome_trace",
    "MetricsRegistry", "MetricsExporter", "MetricsServer",
    "PublishedState", "FleetAggregator",
    "registry_from_serve_events",
]
