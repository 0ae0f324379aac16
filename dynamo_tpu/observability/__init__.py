"""End-to-end request observability.

One request, one ``trace_id``, visible in every layer it touches:

- ``trace``        — the propagated context (``trace_id``/``span_id``/parent)
  minted at the HTTP frontend (honoring an incoming ``x-request-id``) and
  carried through the control-plane request envelope and data-plane prologue.
- ``recorder``     — process-wide span recorder with a bounded buffer,
  JSONL and Chrome-trace (``chrome://tracing`` / Perfetto) exporters, and
  per-request lifecycle summaries (queue wait, prefill, TTFT, KV transfer).
- ``step_metrics`` — engine step telemetry (batch occupancy, running/waiting
  counts, KV pool usage, preemptions) accumulated on the device thread and
  surfaced through the existing Prometheus registries.
- ``perf``        — utilization accounting: analytical FLOPs/bytes cost
  model per model geometry + rolling MFU / bandwidth-utilization / goodput
  (``UtilizationTracker``), exported as ``dyn_worker_*`` gauges.
- ``slo``         — burn-rate SLO tracking over the frontend's TTFT/ITL/
  error stream (``SloTracker``), exported as ``dyn_slo_*`` metrics and the
  frontend's ``/slo`` endpoint.

See docs/observability.md for the metric families, env vars, and formats.
"""

from dynamo_tpu.observability.flight import FlightRecorder, flight_dir, load_dump
from dynamo_tpu.observability.perf import ModelCost, UtilizationTracker, model_cost
from dynamo_tpu.observability.recorder import (
    Span,
    SpanRecorder,
    get_recorder,
    set_recorder,
)
from dynamo_tpu.observability.slo import SloConfig, SloObjective, SloTracker
from dynamo_tpu.observability.step_metrics import StepTelemetry
from dynamo_tpu.observability.trace import TraceContext

__all__ = [
    "FlightRecorder",
    "ModelCost",
    "SloConfig",
    "SloObjective",
    "SloTracker",
    "Span",
    "SpanRecorder",
    "StepTelemetry",
    "TraceContext",
    "UtilizationTracker",
    "flight_dir",
    "get_recorder",
    "load_dump",
    "model_cost",
    "set_recorder",
]
