"""Unified observability layer: metrics registry, Prometheus
exposition, queue-driven autoscaling, and the seeded load harness.

The reference's layer 6 (StatsListener -> StatsStorage -> Play server)
rebuilt for a traced + threaded serving stack: every serving and
training surface publishes through one :class:`MetricsRegistry`, the
HTTP server renders it as Prometheus text at ``GET /metrics``, and the
legacy ``/stats`` JSON is re-derived from the same counters. Named spans
that land in the profiler's trace and feed counters of this registry are
``metrics.spans.SpanClock`` (imported from its module: it needs jax, the
rest of this package does not).
"""

from deeplearning4j_tpu.metrics.registry import (           # noqa: F401
    Counter, Gauge, Histogram, MetricsRegistry, NullRegistry,
    DEFAULT_BUCKETS, DEFAULT_QUANTILES, global_registry, nearest_rank,
)
from deeplearning4j_tpu.metrics.exposition import (         # noqa: F401
    render_text, CONTENT_TYPE,
)
from deeplearning4j_tpu.metrics.autoscale import (          # noqa: F401
    Autoscaler, ScaleDecision, GenerationSlotsTarget, CoalescerTarget,
)
from deeplearning4j_tpu.metrics.loadgen import (            # noqa: F401
    LoadGenerator, LoadResult, ramp_profile, spike_profile,
    poisson_arrivals,
)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "NullRegistry",
    "DEFAULT_BUCKETS", "DEFAULT_QUANTILES", "global_registry",
    "nearest_rank", "render_text", "CONTENT_TYPE", "Autoscaler",
    "ScaleDecision", "GenerationSlotsTarget", "CoalescerTarget",
    "LoadGenerator", "LoadResult", "ramp_profile", "spike_profile",
    "poisson_arrivals",
]
