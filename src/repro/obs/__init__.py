"""End-to-end observability: metrics registry, request tracing, activity
telemetry, the HTTP exposition endpoint — and the analysis plane on top
(time-series recording, SLO burn-rate alerting, anomaly detection,
Perfetto trace export).

See README "Observability" for the metric naming scheme, the SLO spec
format, and examples.
"""
from repro.obs.activity import (
    SCHEDULE_KEYS,
    ActivityObserver,
    static_schedule_counts,
)
from repro.obs.anomaly import (
    Alert,
    AlertManager,
    BurnRateWatcher,
    EwmaDetector,
    SeriesWatcher,
    WatchSpec,
    autoscaler_sink,
    canary_shadow_sink,
    default_drift_watches,
    get_default_alert_manager,
    log_file_sink,
    set_default_alert_manager,
)
from repro.obs.export import to_perfetto, validate_perfetto, write_perfetto
from repro.obs.http import (
    MetricsServer,
    alert_health_check,
    engine_health_check,
    engine_ready_probe,
)
from repro.obs.metrics import (
    DEFAULT_LATENCY_BUCKETS,
    MetricsRegistry,
    default_registry,
    set_default_registry,
)
from repro.obs.slo import (
    DEFAULT_BURN_WINDOWS,
    SLO,
    BurnRateEngine,
    BurnWindow,
    SLOStatus,
    default_serve_slos,
    parse_slo_spec,
    scaled_windows,
)
from repro.obs.timeseries import (
    Series,
    TimeSeriesRecorder,
    get_default_recorder,
    set_default_recorder,
)
from repro.obs.trace import (
    TERMINAL_EVENTS,
    RequestTrace,
    TraceEvent,
    TraceLog,
    begin_trace,
    disable_tracing,
    enable_tracing,
    get_tracer,
    install_process_telemetry,
    span,
    tadd,
    tfinish,
)

__all__ = [
    "MetricsRegistry",
    "default_registry",
    "set_default_registry",
    "DEFAULT_LATENCY_BUCKETS",
    "TraceEvent",
    "RequestTrace",
    "TraceLog",
    "TERMINAL_EVENTS",
    "enable_tracing",
    "disable_tracing",
    "get_tracer",
    "begin_trace",
    "tadd",
    "tfinish",
    "span",
    "install_process_telemetry",
    "ActivityObserver",
    "static_schedule_counts",
    "SCHEDULE_KEYS",
    "MetricsServer",
    "alert_health_check",
    "engine_health_check",
    "engine_ready_probe",
    "Series",
    "TimeSeriesRecorder",
    "get_default_recorder",
    "set_default_recorder",
    "SLO",
    "SLOStatus",
    "BurnWindow",
    "BurnRateEngine",
    "DEFAULT_BURN_WINDOWS",
    "scaled_windows",
    "parse_slo_spec",
    "default_serve_slos",
    "EwmaDetector",
    "Alert",
    "AlertManager",
    "WatchSpec",
    "default_drift_watches",
    "SeriesWatcher",
    "BurnRateWatcher",
    "autoscaler_sink",
    "canary_shadow_sink",
    "log_file_sink",
    "set_default_alert_manager",
    "get_default_alert_manager",
    "to_perfetto",
    "write_perfetto",
    "validate_perfetto",
]
