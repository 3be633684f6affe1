"""repro.obs — the solver telemetry subsystem (DESIGN.md §11).

Structured run logs (JSONL events + manifest), nestable wall-clock trace
spans, monotonic counters/gauges, a leveled console logger mirrored into
the sink, and an opt-in jax.profiler window.  `Telemetry.disabled()` is
the no-op default threaded through SolveEngine and AllocationServer; its
spans, like every recorder's, still reach a profiler trace as
`repro.<name>` TraceMes and the per-name `span_totals()`.
`launch/report.py` renders a post-mortem from any emitted run log.

The live side (DESIGN.md §13): `metrics` is the scrapeable plane —
counters/gauges/fixed-bucket histograms with Prometheus text exposition
and a background `/metrics` exporter — and `memory` is the resource
sampler (host RSS via procfs, device HBM stats where the backend
reports them, per-runner compiled estimates) whose watermarks the
engine stamps into the manifest.
"""
from .telemetry import (JsonlSink, ListSink, Telemetry, LEVELS, ax_paths,
                        note_ax_path, note_op_scopes, op_scopes, span_totals,
                        spanned)
from .schema import (EVENT_FIELDS, RunLog, SchemaError, iter_events,
                     load_run, validate_event, validate_run)
from .profile import ProfilerHook
from .metrics import (Counter, Gauge, Histogram, HistogramSnapshot,
                      MetricsExporter, MetricsRegistry, ExpositionError,
                      parse_exposition, REGISTRY,
                      DEFAULT_LATENCY_BUCKETS)
from .memory import (MemorySample, MemorySampler, compiled_memory_estimate,
                     device_memory_stats, host_rss_bytes,
                     host_peak_rss_bytes, register_memory_gauges)

__all__ = [
    "Telemetry", "JsonlSink", "ListSink", "LEVELS",
    "span_totals", "spanned", "note_op_scopes", "op_scopes", "note_ax_path",
    "ax_paths",
    "EVENT_FIELDS", "RunLog", "SchemaError", "iter_events", "load_run",
    "validate_event", "validate_run",
    "ProfilerHook",
    "Counter", "Gauge", "Histogram", "HistogramSnapshot",
    "MetricsRegistry", "MetricsExporter", "ExpositionError",
    "parse_exposition", "REGISTRY", "DEFAULT_LATENCY_BUCKETS",
    "MemorySample", "MemorySampler", "compiled_memory_estimate",
    "device_memory_stats", "host_rss_bytes", "host_peak_rss_bytes",
    "register_memory_gauges",
]
