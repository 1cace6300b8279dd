"""Unified telemetry: streaming histograms, metric registry, exposition.

The cross-cutting observability layer (ISSUE 2): every server mounts a
:class:`MetricsRegistry` whose contents are served as Prometheus text
format on ``GET /metrics`` and as JSON inside ``/status.json``. See
docs/observability.md for the full metric catalog.
"""

from .guard import TransferGuardCounter
from .hotkeys import SpaceSaving, mount_hot_key_metrics
from .overlap import OverlapTracker
from .histogram import (
    DEFAULT_LATENCY_BOUNDS,
    POW2_COUNT_BOUNDS,
    StreamingHistogram,
    exponential_bounds,
    linear_bounds,
    window_quantile,
)
from .registry import (
    MetricsRegistry,
    escape_label_value,
    format_value,
    render_histogram_lines,
)
from .runtime import (
    build_info,
    hbm_stats,
    process_stats,
    register_process_metrics,
    register_runtime_metrics,
)
from .trace import (
    DeviceProfiler,
    FlightRecorder,
    Trace,
    Tracer,
    activate_traces,
    add_stage_spans,
    mark_active_traces,
    stage_span,
)

__all__ = [
    "DEFAULT_LATENCY_BOUNDS",
    "POW2_COUNT_BOUNDS",
    "DeviceProfiler",
    "FlightRecorder",
    "MetricsRegistry",
    "OverlapTracker",
    "SpaceSaving",
    "StreamingHistogram",
    "Trace",
    "Tracer",
    "TransferGuardCounter",
    "activate_traces",
    "add_stage_spans",
    "build_info",
    "escape_label_value",
    "exponential_bounds",
    "format_value",
    "hbm_stats",
    "linear_bounds",
    "mark_active_traces",
    "mount_hot_key_metrics",
    "process_stats",
    "register_process_metrics",
    "register_runtime_metrics",
    "render_histogram_lines",
    "stage_span",
    "window_quantile",
]

