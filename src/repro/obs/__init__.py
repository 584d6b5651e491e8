"""repro.obs — unified metrics plane + end-to-end request tracing,
plus the active health plane (SLOs, burn-rate alerting, anomaly
detection, Chrome-trace timeline export).

Dependency leaf (stdlib only, like ``repro.guardrails``; JAX is imported
lazily, by the stage spans and runtime counters alone): everything in
the stack can import it. See docs/observability.md.
"""
from repro.obs.metrics import (MetricsRegistry, Counter, Gauge, Histogram,
                               REGISTRY, get_registry, snapshot)
from repro.obs.trace import (Span, RequestTrace, Tracer, TRACER,
                             configure_tracing, get_tracer, stage,
                             RuntimeCounters, RUNTIME)
from repro.obs.export import (prometheus_text, write_metrics,
                              JsonlTraceSink, PeriodicExporter,
                              load_traces)
from repro.obs.slo import (Alert, AlertBus, SLO, SLOEvaluator,
                           HealthMonitor, SampleWindow, default_slos)
from repro.obs.anomaly import (AnomalyMonitor, Detector, EwmaZScore,
                               QueueDepthRunaway, CompileStorm,
                               ReplicaLatencySkew, EscalationTrend,
                               default_detectors, robust_zscore)
from repro.obs.timeline import (chrome_trace, write_chrome_trace,
                                validate_chrome_trace)

__all__ = [
    "MetricsRegistry", "Counter", "Gauge", "Histogram", "REGISTRY",
    "get_registry", "snapshot",
    "Span", "RequestTrace", "Tracer", "TRACER", "configure_tracing",
    "get_tracer", "stage", "RuntimeCounters", "RUNTIME",
    "prometheus_text", "write_metrics", "JsonlTraceSink",
    "PeriodicExporter", "load_traces",
    "Alert", "AlertBus", "SLO", "SLOEvaluator", "HealthMonitor",
    "SampleWindow", "default_slos",
    "AnomalyMonitor", "Detector", "EwmaZScore", "QueueDepthRunaway",
    "CompileStorm", "ReplicaLatencySkew", "EscalationTrend",
    "default_detectors", "robust_zscore",
    "chrome_trace", "write_chrome_trace", "validate_chrome_trace",
]
