"""End-to-end serving observability.

Three low-overhead pieces threaded through the serving path:

* :mod:`trace` — per-request :class:`RequestTrace` (accept -> queue ->
  admit -> prefill -> decode progress -> finish/fail, with speculation
  and recovery annotations) feeding the per-model TTFT / TPOT /
  queue-time windows, retained in a bounded :class:`TraceRing` served
  on ``GET /v2/debug/traces`` and embedded in error responses;
* :mod:`flight` — the engine :class:`FlightRecorder`: a ring of
  per-step records (occupancy, cache pressure, phase timings) plus
  supervisor/watchdog events, snapshotted into every quarantine /
  restart postmortem and dumpable as chrome://tracing JSON on
  ``GET /v2/debug/timeline``;
* :mod:`prom` — Prometheus text exposition for every ServingStats
  counter / gauge / latency window / histogram on ``GET /metrics``.

PR 6 adds the *resource* dimension:

* :mod:`capacity` — KV-cache block telemetry (:class:`CacheTelemetry`,
  ``GET /v2/debug/cache``), the serving FLOPs model behind the MFU /
  goodput gauges (:class:`ServingFlops`), and the jit
  :class:`ProgramRegistry` with retrace blame
  (``GET /v2/debug/programs``);
* :mod:`slo` — declarative per-model objectives evaluated as
  multi-window burn rates on the scheduler's injectable clock
  (:class:`SLOMonitor`, ``GET /v2/slo``).

PR 7 adds the *truth* dimension:

* :mod:`truth` — the :class:`PredictionLedger`: every (predicted,
  measured) pair the simulator/cost model and the runtime can be made
  to agree on, with per-key relative-error distributions and an EWMA
  calibration-drift detector whose alarms carry human blame
  (``GET /v2/debug/predictions``, ``flexflow_sim_*`` on ``/metrics``,
  recalibration suggestions back into search/calibration.py).

PR 12 adds the *step-anatomy* dimension:

* :mod:`steptrace` — the :class:`StepAnatomy` profiler: first-class
  host spans (schedule / admit / prefix_plan / draft / stage /
  dispatch with its parts args, upload and call / post / block /
  readback / account / bookkeep / release / housekeep / observe) plus an independently
  measured device ``execute`` span per scheduler iteration, feeding
  per-``{kind, phase}`` histograms
  (``flexflow_serving_step_phase_seconds``), the conserved account of
  the scheduler thread's seconds (``/v2/stats`` ``step_phases`` with
  its ``unspanned`` remainder, and ``loop``), and an on-demand K-step
  capture rendered as a two-lane real-offset chrome://tracing timeline
  (``GET /v2/debug/anatomy?capture=K``).

PR 20 adds the *fleet* dimension:

* :mod:`journey` — Dapper-style cross-replica request journeys: a
  stable journey id minted (or joined from a W3C ``traceparent``) at
  HTTP/gRPC ingress rides the Request through routing, admission,
  prefill, KV handoff, failover adoption, WAL warm restart, and SSE
  resume, each hop a parent-linked :class:`JourneySpan` in the owning
  replica's :class:`JourneyRecorder` lane (mirrored to a bounded
  on-disk :class:`JourneySpool` next to the WAL so pre-crash spans
  survive process death). :class:`JourneyIndex` stitches the lanes
  into one causal timeline (``GET /v2/debug/journey/{id}``), rendered
  as chrome://tracing JSON or an OTLP-compatible shape.

See tools/obsreport.py for the CLI (summaries, trace waterfalls,
timeline dumps, cache/SLO/anatomy/journey views, and the CI
``--selfcheck``).
"""
from .capacity import (
    GLOBAL_PROGRAMS,
    CacheTelemetry,
    ProgramRegistry,
    ServingFlops,
)
from .flight import FlightRecorder
from .journey import (
    NULL_JOURNEY,
    JourneyContext,
    JourneyIndex,
    JourneyRecorder,
    JourneySpan,
    JourneySpool,
    JourneyStats,
    format_traceparent,
    new_journey_id,
    new_span_id,
    parse_traceparent,
    stitch,
)
from .journey import to_chrome_trace as journey_to_chrome_trace
from .journey import to_otlp as journey_to_otlp
from .prom import (
    escape_label_value,
    format_value,
    render_prometheus,
    sanitize_name,
    validate_exposition,
)
from .slo import DEFAULT_OBJECTIVES, SLObjective, SLOMonitor
from .steptrace import StepAnatomy
from .trace import NULL_TRACE, RequestTrace, TraceRing, next_request_id
from .truth import GLOBAL_LEDGER, PredictionLedger

__all__ = [
    "CacheTelemetry",
    "PredictionLedger",
    "GLOBAL_LEDGER",
    "DEFAULT_OBJECTIVES",
    "FlightRecorder",
    "GLOBAL_PROGRAMS",
    "ProgramRegistry",
    "SLOMonitor",
    "SLObjective",
    "StepAnatomy",
    "ServingFlops",
    "NULL_TRACE",
    "NULL_JOURNEY",
    "JourneyContext",
    "JourneyIndex",
    "JourneyRecorder",
    "JourneySpan",
    "JourneySpool",
    "JourneyStats",
    "format_traceparent",
    "journey_to_chrome_trace",
    "journey_to_otlp",
    "new_journey_id",
    "new_span_id",
    "parse_traceparent",
    "stitch",
    "RequestTrace",
    "TraceRing",
    "next_request_id",
    "escape_label_value",
    "format_value",
    "render_prometheus",
    "sanitize_name",
    "validate_exposition",
]
