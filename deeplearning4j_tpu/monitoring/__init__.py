"""Unified telemetry subsystem.

One shared model for everything the stack observes:

    registry (metrics.py)  <-  spans (tracing.py)
                           <-  device/runtime gauges + recompile watcher
                               (runtime.py)
                           <-  fit loops / MetricsListener (listener.py)
                           <-  ParallelWrapper TrainingStats phases
    registry  ->  GET /metrics on UIServer (Prometheus text exposition)
              ->  JSONL sink / record snapshots (exporters.py)

`ensure_started()` is the one switch: idempotent, called by the fit loops
and the benchmark's runners, it installs the jit-recompile watcher and
declares the default span series so a scrape taken before the first
iteration already shows the full schema.
"""

from __future__ import annotations

import threading

from deeplearning4j_tpu.monitoring.metrics import (  # noqa: F401
    Counter, Gauge, Histogram, MetricsRegistry, global_registry)
from deeplearning4j_tpu.monitoring.events import (  # noqa: F401
    Event, EventLog, emit, events_enabled, global_event_log,
    set_events_enabled)
from deeplearning4j_tpu.monitoring.tracing import (  # noqa: F401
    current_path, declare_default_spans, is_enabled, next_phase, phases,
    record_span, set_enabled, span)
from deeplearning4j_tpu.monitoring.exporters import (  # noqa: F401
    CONTENT_TYPE, JsonlSink, metrics_snapshot, render_prometheus)
from deeplearning4j_tpu.monitoring.listener import (  # noqa: F401
    MetricsListener, maybe_record_fit_iteration, record_fit_iteration)

_started = False
_start_lock = threading.Lock()


def ensure_started() -> None:
    """Idempotently turn on the process-wide default telemetry: the
    recompile watcher and the pre-declared span series."""
    global _started
    if _started:
        return
    with _start_lock:
        if _started:
            return
        from deeplearning4j_tpu.monitoring import runtime
        runtime.install_recompile_watcher()
        declare_default_spans()
        # checkpoint durability series (resilience/durable.py): declared
        # up front so a scrape taken before the first save shows the
        # full schema alongside the span series
        from deeplearning4j_tpu.resilience.durable import (
            declare_checkpoint_series)
        declare_checkpoint_series()
        # elastic membership series (resilience/elastic.py): a scrape on
        # a never-re-meshed fleet still shows generation/member gauges
        from deeplearning4j_tpu.resilience.elastic import (
            declare_elastic_series)
        declare_elastic_series()
        # structured-event series (events.py): the ring depth gauge and
        # dropped counter render before the first event fires
        from deeplearning4j_tpu.monitoring.events import (
            declare_event_series)
        declare_event_series()
        _started = True
