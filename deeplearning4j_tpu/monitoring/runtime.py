"""Device/runtime gauges and the jit-recompilation watcher.

Three signal families, all landing in the shared registry:

- per-device HBM from ``device.memory_stats()`` (bytes_in_use /
  peak_bytes_in_use / peak_bytes_reserved / bytes_limit). On a TPU
  (libtpu 0.0.34) a compiled program's scratch is counted under
  ``bytes_reserved``, not ``bytes_in_use`` — the high-water mark of a
  train step is peak_bytes_in_use + peak_bytes_reserved;
- host RSS, reusing ``ui/stats._current_rss_mb``;
- XLA compiles counted PER FUNCTION NAME, so a per-iteration retrace
  (shape churn, stale jit key) shows up as a climbing
  ``dl4jtpu_jit_compiles_total{fn=...}`` instead of a silent 10x slowdown.

The recompile watcher listens to the three stages jax times when it
builds a program: tracing to a jaxpr, lowering to an MLIR module (where
a Pallas kernel is lowered by Mosaic) and the backend step, which either
loads the executable from the persistent compilation cache or compiles
it. The backend event fires once per program built and carries the
jitted function's name (``jit(name)`` in jax 0.9.0).

The compile ledger: one record per program built, appended to the
process's event log (``monitoring/events.py``) under the category
``compile``, name ``build``, with

- ``fn``: the function's name, as the counter labels it;
- ``outcome``: ``compiled`` (the persistent cache was asked and missed),
  ``loaded`` (the cache held it) or ``uncached`` (no cache key: caching
  off), from the cache events jax records inside the backend step;
- ``trace_s``, ``lower_s``, ``backend_s``: seconds of the three stages.
  Each stage counts its own seconds only: a trace nested in another (an
  inner jit) or in a lowering, and a program built inside a trace (an
  eager op on a constant), are taken out of the stage around them, so no
  second is counted twice. Tracing and lowering go to the next program
  the thread builds;
- ``start``, ``end``: ``time.perf_counter()`` readings, the clock of the
  request traces and of the serving engine's ``health()["setup"]``.

State is per thread: an engine thread and its caller may build at once.
The listeners run only while jax builds a program; a warm step loop
builds none and pays nothing.

No jax import at module load and no backend initialization ever: a
scrape must never be the thing that first touches the accelerator (it
would claim the chip for the scraping process; see ui/server.py's same
guard).
"""

from __future__ import annotations

import sys
import threading
import time
from typing import Optional

from deeplearning4j_tpu.monitoring.events import emit
from deeplearning4j_tpu.monitoring.metrics import (
    MetricsRegistry, global_registry)

COMPILE_COUNTER = "dl4jtpu_jit_compiles_total"
COMPILE_SECONDS = "dl4jtpu_jit_compile_seconds"
BUILDS_COUNTER = "dl4jtpu_jit_builds_total"
#: the compile ledger's event category and name (monitoring/events.py)
LEDGER_CATEGORY = "compile"
LEDGER_EVENT = "build"

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_STAGES = {_TRACE_EVENT: "trace", _LOWER_EVENT: "lower",
           _BACKEND_COMPILE_EVENT: "backend"}
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


def backend_initialized() -> bool:
    """True only if a jax backend ALREADY exists — never triggers init."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge as xb
    return xb.backends_are_initialized()


def update_host_gauges(registry: Optional[MetricsRegistry] = None) -> None:
    from deeplearning4j_tpu.ui.stats import _current_rss_mb
    rss = _current_rss_mb()
    if rss is not None:
        r = registry or global_registry()
        r.gauge("dl4jtpu_host_rss_mb",
                "Host resident set size (MB)").set(rss)


def update_device_gauges(registry: Optional[MetricsRegistry] = None) -> None:
    if not backend_initialized():
        return
    import jax
    r = registry or global_registry()
    in_use = r.gauge("dl4jtpu_device_bytes_in_use",
                     "Device memory currently allocated", ("device",))
    peak = r.gauge("dl4jtpu_device_peak_bytes_in_use",
                   "Device memory high-water mark", ("device",))
    reserved = r.gauge("dl4jtpu_device_peak_bytes_reserved",
                       "Compiled-program scratch high-water mark",
                       ("device",))
    limit = r.gauge("dl4jtpu_device_bytes_limit",
                    "Device memory capacity", ("device",))
    for d in jax.devices():
        ms = d.memory_stats()
        if ms is None:      # the CPU backend keeps no allocator stats
            continue
        name = f"{d.platform}:{d.id}"
        for key, gauge in (("bytes_in_use", in_use),
                           ("peak_bytes_in_use", peak),
                           ("peak_bytes_reserved", reserved),
                           ("bytes_limit", limit)):
            if key in ms:
                gauge.set(float(ms[key]), device=name)


def refresh(registry: Optional[MetricsRegistry] = None) -> None:
    """Bring point-in-time gauges current (called on every scrape)."""
    update_host_gauges(registry)
    update_device_gauges(registry)


_watcher_installed = False
_lock = threading.Lock()


def _fn_label(fun_name: str) -> str:
    """``jit(name)`` -> ``name``: the counter is labelled by the user's
    function, not by the transformation wrapped around it."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


class _Builds(threading.local):
    """One thread's stages in progress and what they leave to the next
    program it builds."""

    def __init__(self):
        #: open stages, innermost last: [stage, start, nested seconds,
        #: cache asked, cache hit]
        self.open = []
        self.trace_s = 0.0
        self.lower_s = 0.0
        #: start of the earliest stage counted toward the next record
        self.start: Optional[float] = None


_builds = _Builds()


def install_recompile_watcher(
        registry: Optional[MetricsRegistry] = None) -> None:
    """Count XLA compiles per function name, time the stages of every
    program built and keep the compile ledger (fit loops and the
    benchmark's runners call this). jax.monitoring offers no
    per-listener unregister, so this is once-per-process: the first
    installer's registry wins."""
    global _watcher_installed
    with _lock:
        if _watcher_installed:
            return
        import jax.monitoring as jm
        r = registry or global_registry()
        compiles = r.counter(
            COMPILE_COUNTER,
            "jax.jit tracing-cache misses (programs built, loaded from "
            "the persistent cache or compiled) per function name",
            ("fn",))
        builds = r.counter(
            BUILDS_COUNTER, "Programs built, by outcome: compiled, "
            "loaded from the persistent cache, or uncached", ("outcome",))
        seconds = r.histogram(
            COMPILE_SECONDS, "Seconds of each stage of building a "
            "program: trace, lower, compile (a cache miss or no cache) "
            "or load (from the persistent cache)", ("stage",))

        def _on_enter(event: str, value: float, **kw) -> None:
            if event in _STAGES:
                _builds.open.append(
                    [_STAGES[event], time.perf_counter(), 0.0, False, False])

        def _on_cache(event: str, **kw) -> None:
            top = _builds.open[-1] if _builds.open else None
            if top is None or top[0] != "backend":
                return
            if event == _CACHE_ASKED:
                top[3] = True
            elif event == _CACHE_HIT:
                top[4] = True

        def _on_exit(event: str, duration: float, **kw) -> None:
            stage = _STAGES.get(event)
            if stage is None:
                return
            if stage == "backend":
                fn = _fn_label(kw["fun_name"])
                compiles.inc(fn=fn)
            b = _builds
            at = next((i for i in range(len(b.open) - 1, -1, -1)
                       if b.open[i][0] == stage), None)
            if at is None:
                return        # the stage began before the watcher
            _, t, nested, asked, hit = b.open[at]
            del b.open[at:]
            end = time.perf_counter()
            if b.open:
                b.open[-1][2] += end - t
            own = end - t - nested
            b.start = t if b.start is None else min(b.start, t)
            if stage == "trace":
                b.trace_s += own
                return
            if stage == "lower":
                b.lower_s += own
                return
            outcome = "loaded" if hit else "compiled" if asked \
                else "uncached"
            builds.inc(outcome=outcome)
            seconds.observe(b.trace_s, stage="trace")
            seconds.observe(b.lower_s, stage="lower")
            seconds.observe(own, stage="load" if hit else "compile")
            emit(LEDGER_CATEGORY, LEDGER_EVENT, fn=fn, outcome=outcome,
                 trace_s=b.trace_s, lower_s=b.lower_s, backend_s=own,
                 start=b.start, end=end)
            b.trace_s = b.lower_s = 0.0
            b.start = None

        jm.register_scalar_listener(_on_enter)
        jm.register_event_listener(_on_cache)
        jm.register_event_duration_secs_listener(_on_exit)
        _watcher_installed = True
