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

The recompile watcher is a ``jax.monitoring`` duration listener on the
backend-compile event, which fires once per tracing-cache miss and
carries the jitted function's name (``jit(name)`` in jax 0.9.0).

No jax import at module load and no backend initialization ever: a
scrape must never be the thing that first touches the accelerator (it
would claim the chip for the scraping process; see ui/server.py's same
guard).
"""

from __future__ import annotations

import sys
import threading
from typing import Optional

from deeplearning4j_tpu.monitoring.metrics import (
    MetricsRegistry, global_registry)

COMPILE_COUNTER = "dl4jtpu_jit_compiles_total"
COMPILE_SECONDS = "dl4jtpu_jit_compile_seconds"

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


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


def install_recompile_watcher(
        registry: Optional[MetricsRegistry] = None) -> None:
    """Count XLA compiles per function name and record their durations
    (fit loops and bench drivers call this). jax.monitoring offers no
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
            "jax.jit tracing-cache misses (compiles) per function name",
            ("fn",))
        seconds = r.histogram(
            COMPILE_SECONDS, "XLA backend compile durations")

        def _on_duration(event: str, duration: float, **kw) -> None:
            if event == _BACKEND_COMPILE_EVENT:
                compiles.inc(fn=_fn_label(kw["fun_name"]))
                seconds.observe(duration)

        jm.register_event_duration_secs_listener(_on_duration)
        _watcher_installed = True
