# The observability plane (FfDL §4): the sensor layer the platform's
# operators — human and autonomous — read. Six parts:
#   * bus:     per-shard, sequence-numbered, retention-bounded event bus
#              (promoted from core.types.EventLog) with tenant-scoped
#              visibility, served as GET /v2/events with cursor replay;
#   * meter:   per-tenant usage metering (chip-seconds, job outcomes, log
#              bytes, 429s), served as GET /v1/usage and via /metrics;
#   * metrics: a dependency-free Prometheus text exposition (counters,
#              gauges, histograms) behind GET /metrics;
#   * sse:     Server-Sent-Events framing for the true-streaming transport
#              behind `ffdl logs --follow` / `status --watch` / `events
#              --follow` (long-poll remains the fallback contract);
#   * operator: the autonomous reconciler (shard autoscaling, hot-tenant
#              isolation, health-gated rolling upgrades) closing the loop
#              over the sensors above via the /v2/admin verbs;
#   * spans:   program spans and counters inside the platform's tick and
#              the learner, on the profiler's clock, kept per tick in a
#              bounded ring and served via /metrics (tick phases, GC).
from repro.obs.bus import (
    DEFAULT_RETENTION,
    Event,
    EventBus,
    PLATFORM_EVENT_KINDS,
    event_to_wire,
)
from repro.obs.meter import USAGE_FIELDS, UsageMeter, install_meter
from repro.obs.operator import (
    OPERATOR_EVENT_KINDS,
    Operator,
    OperatorConfig,
    OperatorPolicy,
)
from repro.obs.metrics import (
    DEFAULT_BUCKETS,
    Histogram,
    METRIC_NAMES,
    render_metrics,
)
from repro.obs.spans import (
    RING_TICKS,
    TickRecord,
    count,
    gc_pause_totals,
    phase_histograms,
    recent_ticks,
    span,
)
from repro.obs.sse import (
    SSE_CONTENT_TYPE,
    SseMessage,
    format_comment,
    format_event,
    iter_sse,
)

__all__ = [
    "DEFAULT_BUCKETS",
    "DEFAULT_RETENTION",
    "Event",
    "EventBus",
    "Histogram",
    "METRIC_NAMES",
    "OPERATOR_EVENT_KINDS",
    "Operator",
    "OperatorConfig",
    "OperatorPolicy",
    "PLATFORM_EVENT_KINDS",
    "RING_TICKS",
    "SSE_CONTENT_TYPE",
    "SseMessage",
    "TickRecord",
    "USAGE_FIELDS",
    "UsageMeter",
    "count",
    "event_to_wire",
    "format_comment",
    "format_event",
    "gc_pause_totals",
    "install_meter",
    "iter_sse",
    "phase_histograms",
    "recent_ticks",
    "render_metrics",
    "span",
]
