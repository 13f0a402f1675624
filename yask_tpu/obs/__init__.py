"""Observability spine: span tracer + metrics registry + the
interpretive layer over them (fleet telemetry merge, SLO burn-rate
monitor, span math).  See ``docs/observability.md``;
terminal/Perfetto rendering lives in ``tools/obs_report.py`` and
Prometheus exposition in ``tools/obs_export.py``."""

from yask_tpu.obs.tracer import (  # noqa: F401
    PHASES, TRACE_BASENAME, TRACE_SCHEMA, activate, compact_if_large,
    current_span_id, current_trace_id, default_trace_path,
    kept_spans, new_trace_id, phase_for_site, read_spans,
    record_span, set_trace, span, stamp_trace, trace_enabled,
    trace_max_bytes,
)
from yask_tpu.obs.metrics import (  # noqa: F401
    REGISTRY, Counter, Gauge, Histogram, Registry, get_registry,
    percentile,
)
from yask_tpu.obs.telemetry import (  # noqa: F401
    TELEMETRY_SCHEMA, merge_snapshots, prom_name, to_prometheus,
)
from yask_tpu.obs.slo import (  # noqa: F401
    SLO_SCHEMA, SloMonitor, slo_enabled,
)
