"""Telemetry subsystem (docs/OBSERVABILITY.md): cross-thread span tracing
with a flight-recorder ring (tracer.py — Chrome trace-event JSON, Perfetto-
loadable), analytic MFU/throughput accounting with a jax.monitoring
recompile counter (mfu.py), and the run-health plane — streaming anomaly
detection over the metric stream (health.py) plus a live /metrics ·
/healthz · /statusz HTTP exporter (exporter.py), and the per-sample
lineage ledger — end-to-end rollout provenance with drop attribution
(lineage.py, queried by tools/inspect_run.py). tracer/health/exporter/
lineage are jax-free; mfu.py imports jax lazily, so a process that must
not touch the backend can load any of them."""

from nanorlhf_tpu.telemetry.exporter import (
    StatusExporter,
    render_prometheus,
    render_prometheus_histograms,
    validate_prometheus_text,
)
from nanorlhf_tpu.telemetry.health import (
    DEFAULT_RULES,
    SLO_RULES,
    HealthConfig,
    HealthMonitor,
    HealthRule,
)
from nanorlhf_tpu.telemetry.hist import (
    LatencyHub,
    StreamingHistogram,
    percentiles_from_samples,
)
from nanorlhf_tpu.telemetry.lineage import (
    LineageLedger,
    chains,
    drop_histogram,
    read_ledger,
)
from nanorlhf_tpu.telemetry.mfu import (
    BACKEND_COMPILE_EVENT,
    CPU_PEAK_FLOPS,
    PEAK_FLOPS_PER_CHIP,
    RecompileCounter,
    flops_param_count,
    peak_flops_per_chip,
    recompile_counter,
    update_flops,
)
from nanorlhf_tpu.telemetry.tracer import (
    SpanTracer,
    validate_trace_events,
    validate_trace_file,
)

__all__ = [
    "BACKEND_COMPILE_EVENT",
    "CPU_PEAK_FLOPS",
    "DEFAULT_RULES",
    "HealthConfig",
    "HealthMonitor",
    "HealthRule",
    "LatencyHub",
    "LineageLedger",
    "PEAK_FLOPS_PER_CHIP",
    "RecompileCounter",
    "SLO_RULES",
    "SpanTracer",
    "StatusExporter",
    "StreamingHistogram",
    "chains",
    "drop_histogram",
    "flops_param_count",
    "peak_flops_per_chip",
    "percentiles_from_samples",
    "read_ledger",
    "recompile_counter",
    "render_prometheus",
    "render_prometheus_histograms",
    "update_flops",
    "validate_prometheus_text",
    "validate_trace_events",
    "validate_trace_file",
]
