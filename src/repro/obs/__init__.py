"""Swarm observability layer.

One spine, four artifacts:

* :mod:`repro.obs.trace`   — span-based :class:`TraceRecorder` (dual sim /
  wall clocks, thread-safe ring buffer, deterministic ordering);
* :mod:`repro.obs.metrics` — in-process counters / gauges / histograms;
* :mod:`repro.obs.record`  — the ElasticController flight recorder (every
  broker decision as a structured, replayable record);
* :mod:`repro.obs.export`  — Chrome/Perfetto ``trace_event`` JSON + raw
  JSONL export and the schema validator CI gates on;
* :mod:`repro.obs.report`  — the run-report CLI rendering timeline, overlap,
  straggler heatmap, and decision log from the artifacts;
* :mod:`repro.obs.bus`     — telemetry fan-out so the broker's TelemetryLog,
  the metrics registry, and user sinks all subscribe to one stream;
* :mod:`repro.obs.slog`    — structured ``event k=v`` logging for launchers;
* :mod:`repro.obs.watchdog` — streaming SLO rules + EWMA/MAD anomaly
  detectors emitting typed :class:`WatchdogRecord` trips;
* :mod:`repro.obs.critpath` / :mod:`repro.obs.whatif` — critical-path
  bottleneck attribution over span logs and counterfactual re-pricing
  (imported explicitly, not re-exported: they pull in :mod:`repro.check`
  and :mod:`repro.core` lazily);
* :mod:`repro.obs.scopes`  — the RAD step's device scope names and the
  scope an op_name names (imported explicitly).

Everything here is dependency-free (stdlib + the repo's own dataclasses;
JAX only inside :func:`repro.obs.trace.host_span`) and no-ops when
disabled, so instrumented hot paths cost nothing in production runs that
don't ask for a trace.
"""
from .bus import MetricsTelemetrySink, TelemetryBus
from .export import (events_from_dicts, read_header, read_jsonl,
                     surface_drops, to_trace_events, validate_trace_events,
                     write_chrome_trace, write_jsonl)
from .metrics import Counter, Gauge, Histogram, MetricsRegistry
from .record import (CalibrationRecord, CandidateScore, DetectorRecord,
                     EpochFlightRecord, FlightRecorder, ReplanRecord,
                     RouteRecord, WatchdogRecord)
from .slog import StructuredLogger, add_logging_args, get_logger
from .watchdog import Watchdog
from .trace import (CAT_BWD, CAT_CHECKPOINT, CAT_CONTROLLER, CAT_DECODE,
                    CAT_ENCODE, CAT_FWD, CAT_MIGRATION, CAT_SERVE_PREFILL,
                    CAT_SERVE_REPLAY, CAT_TRANSFER, CATEGORIES, CLOCK_SIM,
                    CLOCK_WALL, TraceEvent, TraceRecorder)

__all__ = [
    "CAT_BWD", "CAT_CHECKPOINT", "CAT_CONTROLLER", "CAT_DECODE",
    "CAT_ENCODE", "CAT_FWD", "CAT_MIGRATION", "CAT_SERVE_PREFILL",
    "CAT_SERVE_REPLAY", "CAT_TRANSFER", "CATEGORIES",
    "CLOCK_SIM", "CLOCK_WALL", "CalibrationRecord", "CandidateScore",
    "Counter", "DetectorRecord", "EpochFlightRecord", "FlightRecorder",
    "Gauge", "Histogram", "MetricsRegistry", "MetricsTelemetrySink",
    "ReplanRecord", "RouteRecord", "StructuredLogger", "TelemetryBus",
    "TraceEvent", "TraceRecorder", "Watchdog", "WatchdogRecord",
    "add_logging_args", "events_from_dicts", "get_logger", "read_header",
    "read_jsonl", "surface_drops", "to_trace_events", "validate_trace_events",
    "write_chrome_trace", "write_jsonl",
]
