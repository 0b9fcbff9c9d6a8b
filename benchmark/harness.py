"""The data-driven part of the harness: ``BENCHMARK.json`` names a cell's
configuration, traffic and metrics, and every one of them is a file of its
own that is found by that name. Adding a cell, a configuration, a traffic
mix or a per-layer metric adds files and entries and edits nothing here.

- configuration: the ``file`` its ``configs`` entry names; its ``model``
                 names ``<path>/models/<model>.py``, which builds the
                 program's net from the file's sizes, and the plain
                 reference beside it, ``<path>/reference/<model>.py``.
                 The file holds the sizes as they are run. ``reduced``
                 (the entry's list, again) names each key whose value
                 is the chip's share and not the source's: the depth,
                 the experts or heads held here, the slice of the
                 vocabulary; never a width. Where it is not empty,
                 ``published`` holds the source's value of just those
                 keys, and ``deployment`` says in one line over how
                 many chips each layer is divided and how, and what
                 this chip holds. A file with nothing reduced has no
                 ``published`` (tests/benchmark/test_benchmark_contract.py
                 holds a file to this; nothing here reads the three keys)
- traffic mix:   ``<path>/traffic/<traffic>.json`` under a directory of
                 ``paths``; its ``kind`` names the one general generator
                 that reads it, ``<path>/runners/<kind>.py`` with
                 ``run(cell, args, devices, clock0, tracer)``
- limits:        ``<path>/limits/<workload>.json``
- per-layer metric: ``<path>/metrics/<name>.py`` with ``read(ctx)``, which
                 returns the value or ``None`` when it finds nothing to read

``<path>`` is any directory of ``paths``, looked through in order.
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import tempfile
import threading
import time
from typing import Dict, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: modules found by name, by the file they were loaded from: a runner,
#: a model and its reference are each one object in a process
_MODULES: Dict[str, object] = {}

#: the part of the window a ``--trace 1`` run traces: long enough for
#: tens of steps, short enough that reading the trace stays cheap
TRACE_START_S = 2.0
TRACE_LENGTH_S = 6.0


class Cell:
    def __init__(self, bench: dict, name: str, root: str = ROOT,
                 dry_run: bool = False):
        entry = next((w for w in bench["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(known: {[w['name'] for w in bench['workloads']]})")
        cfg_entry = next(c for c in bench["configs"]
                         if c["name"] == entry["config"])
        self.name, self.chips = name, entry["chips"]
        self.root, self.paths = root, bench["paths"]
        self.config = _load_json(os.path.join(root, cfg_entry["file"]))
        self.traffic = _load_json(self.find(
            os.path.join("traffic", entry["traffic"] + ".json")))
        limits = _load_json(self.find(
            os.path.join("limits", name + ".json")))
        if dry_run:
            _merge(self.config, self.config.get("dry_run", {}))
            _merge(self.traffic, self.traffic.get("dry_run", {}))
            _merge(limits, limits.get("dry_run", {}))
        #: every number compared has a limit of its own, kept with the
        #: readings it was set from in limits/<workload>.json
        self.limits = limits["limits"]
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]

    def find(self, relative: str) -> str:
        for p in self.paths:
            path = os.path.join(self.root, p, relative)
            if os.path.exists(path):
                return path
        raise FileNotFoundError(
            f"{relative} under none of {self.paths}")

    def module(self, kind: str, name: str):
        """``<path>/<kind>/<name>.py`` as a module, loaded once."""
        path = os.path.realpath(self.find(os.path.join(kind, name + ".py")))
        if path not in _MODULES:
            spec = importlib.util.spec_from_file_location(
                f"benchmark_{kind}_" + name.replace(".", "_").replace(
                    "-", "_"), path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            _MODULES[path] = mod
        return _MODULES[path]

    def reader(self, metric: str):
        return self.module("metrics", metric).read

    def runner(self):
        """The general generator of this cell's kind of traffic."""
        return self.module("runners", self.traffic["kind"])

    def model(self):
        """What builds the program's net from the configuration."""
        return self.module("models", self.config["model"])

    def reference(self):
        """The configuration's plain reference: imports nothing of the
        program."""
        return self.module("reference", self.config["model"])


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _merge(into: dict, over: dict) -> None:
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(into.get(k), dict):
            _merge(into[k], v)
        else:
            into[k] = v


def load_benchmark(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def claim_devices(cell: Cell, dry_run: bool = False):
    """Point jax's compile cache into the checkout and return the cell's
    devices, or ``None`` (with the reason on standard error) when the
    backend is not a TPU or holds fewer chips than the cell asks for. A
    dry run wants the CPU instead, and prints no device number."""
    import sys
    from deeplearning4j_tpu.util.compile_cache import (
        configure_compile_cache)
    configure_compile_cache()
    import jax
    # every program goes to the cache, also the small ones, so that a
    # cell's second run in a checkout compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    want = "cpu" if dry_run else "tpu"
    if devices[0].platform != want or len(devices) < cell.chips:
        print(f"benchmark: need {cell.chips} {want} device(s), found "
              f"{len(devices)} of platform {devices[0].platform!r}; "
              f"no result", file=sys.stderr)
        return None
    return devices[:cell.chips]


class Tracer:
    """Traces ``TRACE_LENGTH_S`` of the window from a thread of its own,
    so that neither the fit loop nor the generator stops for it."""

    def __init__(self, seconds: float):
        self.start_after = min(TRACE_START_S, seconds / 4.0)
        self.length = min(TRACE_LENGTH_S, seconds / 2.0)
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self._thread: Optional[threading.Thread] = None
        self.error: Optional[BaseException] = None

    def arm(self, t0: float) -> None:
        self._thread = threading.Thread(target=self._run, args=(t0,),
                                        daemon=True, name="bench-tracer")
        self._thread.start()

    def _run(self, t0: float) -> None:
        import jax
        try:
            time.sleep(max(0.0, t0 + self.start_after
                           - time.perf_counter()))
            # the device's ops and the runtime's own host spans, not a
            # Python call trace: that slows a host-bound engine severalfold
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.enable_hlo_proto = False
            jax.profiler.start_trace(self.dir, profiler_options=options)
            self.t_start = time.perf_counter()
            time.sleep(self.length)
            self.t_stop = time.perf_counter()
            jax.profiler.stop_trace()
        except BaseException as e:  # noqa: BLE001 — re-raised in finish
            self.error = e

    def finish(self):
        """Wait for the trace to be written; returns the reduced trace."""
        from benchmark.xplane import Trace
        if self._thread is None:
            raise RuntimeError("the window never opened: nothing traced")
        self._thread.join(timeout=300)
        if self.error is not None:
            raise self.error
        self.trace = Trace.from_dir(self.dir)
        shutil.rmtree(self.dir, ignore_errors=True)
        return self.trace


def peak_memory(devices) -> int:
    """Peak bytes on the fullest chip: ``peak_bytes_in_use`` leaves out
    the scratch of compiled programs, which this runtime books under
    ``peak_bytes_reserved`` (PERF.md, PR 21), so the high-water mark is
    their sum."""
    peak = 0
    for d in devices:
        ms = d.memory_stats() or {}
        peak = max(peak, int(ms.get("peak_bytes_in_use", 0))
                   + int(ms.get("peak_bytes_reserved", 0)))
    return peak


def memory_line(devices) -> str:
    """The fullest chip's ``memory_stats()`` as one line for standard
    error: what is held now, the in-use peak and the programs' scratch."""
    d = max(devices, key=lambda d: (d.memory_stats() or {}).get(
        "peak_bytes_in_use", 0))
    ms = d.memory_stats() or {}
    keep = ("bytes_in_use", "peak_bytes_in_use", "peak_bytes_reserved",
            "bytes_limit")
    return "memory_stats " + json.dumps(
        {k: int(ms[k]) for k in keep if k in ms})


def device_block(devices, record: dict, trace=None) -> dict:
    d = devices[0]
    out = {"platform": d.platform, "kind": d.device_kind,
           "count": len(devices),
           "memory_peak_bytes": record["memory_peak_bytes"]}
    if trace is not None:
        out["busy_s"] = trace.busy_s()
        out["window_s"] = trace.window_s()
    return out


def per_layer_metrics(cell: Cell, ctx: dict) -> Dict[str, dict]:
    out = {}
    for m in cell.per_layer:
        value = cell.reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result(cell: Cell, record: dict, devices, trace=None,
           peaks: Optional[dict] = None) -> dict:
    """The object of the last line. ``checks`` comes last, the run's
    ``notes`` (where it has any) before it."""
    checks = record["checks"]
    if trace is None:
        metrics = {"setup_s": {"value": record["setup_s"], "unit": "s"}}
        for m in cell.end_to_end:
            if m["name"] in record["end_to_end"]:
                metrics[m["name"]] = {
                    "value": record["end_to_end"][m["name"]],
                    "unit": m["unit"]}
    else:
        ctx = {"cell": cell, "config": cell.config,
               "traffic": cell.traffic, "record": record, "trace": trace,
               "peaks": peaks, "chips": cell.chips,
               "trace_interval": record.get("trace_interval")}
        metrics = per_layer_metrics(cell, ctx)
    out = {"correct": all(c.ok for c in checks),
           "attempted": int(record["attempted"]),
           "failed": int(record["failed"]),
           "metrics": metrics,
           "device": device_block(devices, record, trace)}
    if trace is not None:
        out["breakdown"] = trace.breakdown()
    if record.get("notes"):
        # what a reader of the ledger needs beside the metrics, such as
        # how many requests a tail was taken over; the driver ignores it
        out["notes"] = record["notes"]
    out["checks"] = {c.name: c.as_dict() for c in checks}
    return out
