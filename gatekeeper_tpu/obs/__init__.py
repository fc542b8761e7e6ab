"""Observability: in-process tracing (spans, ring retention, JSON +
OTLP export, W3C traceparent propagation), per-constraint device-time
cost attribution, the trip-triggered flight recorder, and the
per-admission decision log. See docs/observability.md for the span
taxonomy and wiring map."""

from .attribution import MONO_PARTITION, CostAttributor
from .decisionlog import (
    DECISION_SCHEMA_FIELDS,
    DecisionLog,
    check_decision_schema,
)
from .flightrecorder import FlightRecorder
from .slo import QuantileSketch, SloEngine, SloTarget, export_slo
from .tracer import (
    NOOP_SPAN,
    GcPauses,
    Span,
    SpanContext,
    Tracer,
    derive_trace_id,
    format_traceparent,
    parse_traceparent,
    span_breakdown,
    stage,
    start_span,
)

__all__ = [
    "NOOP_SPAN",
    "DECISION_SCHEMA_FIELDS",
    "MONO_PARTITION",
    "CostAttributor",
    "DecisionLog",
    "FlightRecorder",
    "GcPauses",
    "stage",
    "QuantileSketch",
    "SloEngine",
    "SloTarget",
    "check_decision_schema",
    "export_slo",
    "Span",
    "SpanContext",
    "Tracer",
    "derive_trace_id",
    "format_traceparent",
    "parse_traceparent",
    "span_breakdown",
    "start_span",
]
