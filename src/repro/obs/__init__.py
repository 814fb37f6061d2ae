"""repro.obs — observability for the serving/kernel/chip stack.

Zero-dependency telemetry in two parts, tied together by a recorder:

* ``metrics``  — process-local Counter/Gauge/Histogram registry with
                 log-spaced latency buckets, JSON ``snapshot()`` and
                 Prometheus text ``exposition()``.
* ``trace``    — span-based flight recorder (bounded ring buffer) that
                 exports Chrome ``trace_event`` JSON for Perfetto.

``recorder.EngineRecorder`` is what you hand to ``serve.engine.Engine``;
the default ``NullRecorder`` keeps the hot path untouched. The recording
one also marks each tick phase as a ``jax.profiler`` annotation
(``engine.<phase>``) and counts XLA compiles per program through a
``jax.monitoring`` listener; it imports jax when constructed, never at
import time. ``hw.chip`` publishes chip placement/utilization telemetry
into the same registry, so one ``EngineRecorder.snapshot()`` describes the
whole stack.

Fleet-health additions (all stdlib-only):

* ``sketch``   — mergeable DDSketch-style quantile sketch with a 1%
                 relative-error guarantee; per-replica latency sketches
                 merge into one fleet snapshot.
* ``slo``      — SLO objectives over rolling tick windows with
                 multi-window burn-rate alerts (``SLOMonitor``).
* ``export``   — live ``http.server`` Prometheus endpoint
                 (``MetricsHTTPServer``) + periodic JSON snapshots
                 (``PeriodicSnapshotWriter``).

Every module here is stdlib-only at import.
"""
from repro.obs.export import (MetricsHTTPServer,  # noqa: F401
                              PeriodicSnapshotWriter)
from repro.obs.metrics import (Counter, DEFAULT_LATENCY_BUCKETS,  # noqa: F401
                               Gauge, Histogram, MetricsRegistry,
                               log_buckets)
from repro.obs.recorder import (EngineRecorder, NullRecorder,  # noqa: F401
                                SNAPSHOT_SCHEMA)
from repro.obs.sketch import DEFAULT_ALPHA, QuantileSketch  # noqa: F401
from repro.obs.slo import (SLOMonitor, SLOObjective,  # noqa: F401
                           SLOTracker, default_serving_slos)
from repro.obs.trace import TraceRecorder  # noqa: F401

__all__ = [
    "Counter", "DEFAULT_ALPHA", "DEFAULT_LATENCY_BUCKETS", "EngineRecorder",
    "Gauge", "Histogram", "MetricsHTTPServer", "MetricsRegistry",
    "NullRecorder", "PeriodicSnapshotWriter", "QuantileSketch",
    "SLOMonitor", "SLOObjective", "SLOTracker", "SNAPSHOT_SCHEMA",
    "TraceRecorder", "default_serving_slos", "log_buckets",
]
