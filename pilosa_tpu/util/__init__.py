from . import events, stats, tracing
from .events import JOURNAL, Event, EventJournal
from .logger import Logger, NopLogger, StandardLogger, VerboseLogger
from .stats import (
    REGISTRY,
    ExpvarStatsClient,
    Histogram,
    MetricsRegistry,
    MultiStatsClient,
    NopStatsClient,
    PipelineStats,
    StatsClient,
)
from .tracing import NopTracer, Span, TraceContext, Tracer

__all__ = [
    "Event",
    "EventJournal",
    "ExpvarStatsClient",
    "Histogram",
    "JOURNAL",
    "Logger",
    "MetricsRegistry",
    "MultiStatsClient",
    "NopLogger",
    "NopStatsClient",
    "NopTracer",
    "PipelineStats",
    "REGISTRY",
    "Span",
    "StandardLogger",
    "StatsClient",
    "TraceContext",
    "Tracer",
    "VerboseLogger",
    "events",
    "stats",
    "tracing",
]
