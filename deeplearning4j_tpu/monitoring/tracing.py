"""Nestable span tracing bridged to both the metrics registry and XPlane.

    with span("forward"):
        ...

records the wall-clock duration into the `dl4jtpu_span_seconds{span=...}`
histogram of the global registry AND emits a `jax.profiler.TraceAnnotation`
so the same region lines up with XPlane traces captured by
`optimize.profiler.ProfilerListener` (TensorBoard/xprof shows the span as
a named host-side slice inside the trace window).

Spans nest via a thread-local stack (`current_path()` returns e.g.
"iteration/forward"); the histogram label stays the LEAF name so series
cardinality is bounded by the set of span names, not call paths.

`phases()` / `next_phase(name)` keep a thread in a FLAT sequence of
spans — exactly one open at a time, each switch closing the last — for a
cycle whose phase boundaries cross function boundaries (the serving
engine's step: admission, prefill, decode dispatch, sampling). A profiler
reduction that names a device-idle gap by the innermost host event over it
then reads the program's own phase names.

`set_enabled(False)` turns spans into no-ops (for overhead-sensitive
loops).
"""

from __future__ import annotations

import contextlib
import threading
import time
import weakref
from typing import Optional

from deeplearning4j_tpu.monitoring.metrics import (
    MetricsRegistry, global_registry)

SPAN_HISTOGRAM = "dl4jtpu_span_seconds"
SPAN_ERRORS = "dl4jtpu_span_errors_total"

#: the span names the fit loops emit; declared eagerly so the /metrics
#: exposition always carries them ("step" is the fused train step)
DEFAULT_SPANS = ("etl", "step", "listener")

_tls = threading.local()
_enabled = True

#: registry -> {span name: bound histogram child}: a span's exit observes
#: through a child resolved once, not through the registry's
#: get-or-create lock (the hot-path rule of serving/health.py)
_children: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()

# jax.profiler.TraceAnnotation, resolved lazily: the metrics side of a
# span must work in processes where jax never imported (bench failure
# paths). None = unresolved, False = unavailable.
_annotation_cls = None


def _get_annotation_cls():
    global _annotation_cls
    if _annotation_cls is None:
        try:
            from jax.profiler import TraceAnnotation
            _annotation_cls = TraceAnnotation
        except Exception:  # noqa: BLE001 — no jax: spans still time
            _annotation_cls = False
    return _annotation_cls


def set_enabled(flag: bool) -> None:
    global _enabled
    _enabled = bool(flag)


def is_enabled() -> bool:
    return _enabled


def current_path() -> str:
    """Slash-joined stack of open spans on this thread ("" outside any)."""
    return "/".join(getattr(_tls, "stack", ()))


def span_histogram(registry: Optional[MetricsRegistry] = None):
    r = registry or global_registry()
    return r.histogram(
        SPAN_HISTOGRAM,
        "Wall-clock seconds of named training-loop spans "
        "(host-side; aligns with XPlane TraceAnnotations)", ("span",))


def _span_child(name: str, registry: MetricsRegistry):
    by_name = _children.setdefault(registry, {})
    child = by_name.get(name)
    if child is None:
        child = by_name[name] = span_histogram(registry).labels(span=name)
    return child


def record_span(name: str, seconds: float,
                registry: Optional[MetricsRegistry] = None) -> None:
    """Directly record a span observation (used by TrainingStats and any
    timer that measured the interval itself)."""
    _span_child(name, registry or global_registry()).observe(seconds)


def declare_default_spans(registry: Optional[MetricsRegistry] = None) -> None:
    h = span_histogram(registry)
    for name in DEFAULT_SPANS:
        h.labels(span=name)


class span:
    """Context manager: time a region into the registry + XPlane."""

    __slots__ = ("name", "registry", "_t0", "_ann")

    def __init__(self, name: str,
                 registry: Optional[MetricsRegistry] = None):
        self.name = name
        self.registry = registry

    def __enter__(self):
        if not _enabled:
            self._t0 = None
            return self
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        stack.append(self.name)
        self._ann = None
        cls = _get_annotation_cls()
        if cls:
            try:
                self._ann = cls(self.name)
                self._ann.__enter__()
            except Exception:  # noqa: BLE001 — annotation is best-effort
                self._ann = None
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._t0 is None:
            return False
        dt = time.perf_counter() - self._t0
        if self._ann is not None:
            try:
                self._ann.__exit__(exc_type, exc, tb)
            except Exception:  # noqa: BLE001
                pass
        _tls.stack.pop()
        r = self.registry or global_registry()
        _span_child(self.name, r).observe(dt)
        if exc_type is not None:
            r.counter(SPAN_ERRORS,
                      "Spans that exited via an exception",
                      ("span",)).inc(span=self.name)
        return False


#: `_tls.phase` inside a `phases` block before its first `next_phase`
_ARMED = object()


class phases(contextlib.ContextDecorator):
    """A flat sequence of spans on this thread:

        with phases():
            ...                         # no span until there is work
            next_phase("engine.reap")   # opens the first
            ...
            next_phase("engine.admit")  # closes reap, opens admit
            ...                         # callees switch on from here

    From the first `next_phase` on, exactly one span of the sequence is
    open at any time; leaving the block closes whichever is. A block that
    never calls `next_phase` records nothing. Re-entrant: inside a running
    sequence `phases()` does nothing and leaves the closing to the outer
    block, so a function that may be called from inside a cycle or on its
    own can be decorated with it."""

    def __init__(self):
        self._owner = False

    def _recreate_cm(self):        # one instance per decorated call
        return phases()

    def __enter__(self):
        if _enabled and getattr(_tls, "phase", None) is None:
            self._owner = True
            _tls.phase = _ARMED
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._owner:
            self._owner = False
            s, _tls.phase = _tls.phase, None
            if s is not _ARMED:
                s.__exit__(exc_type, exc, tb)
        return False


def next_phase(name: str) -> None:
    """Inside a `phases` sequence on this thread: close the open span, if
    any, and open `name` (nothing if `name` is already open). Outside
    one: nothing, so shared code names its phases only for a caller that
    runs a cycle."""
    cur = getattr(_tls, "phase", None)
    if cur is None:
        return
    if cur is not _ARMED:
        if cur.name == name:
            return
        cur.__exit__(None, None, None)
    s = _tls.phase = span(name)
    s.__enter__()
