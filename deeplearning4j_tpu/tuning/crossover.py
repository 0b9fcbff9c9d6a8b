"""Per-shape kernel-crossover store: measured kernel-vs-fallback timings,
persisted like TPULINT_BASELINE.

Every hand kernel in this repo ships with an equal-semantics fallback
(the XLA graph), and the round-3 lesson (PERF.md) is that which side
wins is a property of the SHAPE and the HARDWARE, not of the kernel:
``pallas_call`` boundaries can cost more than the traffic they save.
The store turns that into data:

- an **entry** is one paired measurement: ``kernel_ms`` vs
  ``fallback_ms`` for a fingerprinted (domain, shape, dtype) point,
  stamped with the platform + device kind it was measured on and the
  implementation revision of the kernel it timed;
- ``choose(key)`` is the hot-path read: "auto" plan/impl resolution
  asks it which side to run. A missing, platform-mismatched, or
  stale-revision entry yields the caller's default (the current static
  behavior) — calibration can only ever *refine* the defaults, never
  silently change an uncalibrated run;
- ``record``/``calibrate`` ratchet measurements in (running mean over
  samples) and persist atomically, the baseline pattern: one live TPU
  window writes ``KERNEL_CROSSOVER.json`` and every later process —
  including ones with no TPU — resolves "auto" from it.

Telemetry: ``dl4jtpu_autotune_decisions_total{domain,choice}`` counts
every ``choose`` (choice = kernel | fallback | default) and
``dl4jtpu_autotune_calibrations_total{domain,choice}`` every recorded
measurement (choice = the measured winner), so a run's records show
which plans the store actually picked.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from typing import Any, Callable, Dict, Optional

log = logging.getLogger(__name__)

CROSSOVER_NAME = "KERNEL_CROSSOVER.json"
CROSSOVER_VERSION = 1

#: implementation revision per kernel domain. Bump when the kernel (or
#: its fallback) changes enough that old timings no longer describe it —
#: load() prunes entries recorded against another revision (the
#: stale-entry ratchet: a rewritten kernel re-earns its calibration).
IMPL_REVS: Dict[str, int] = {
    "train_bottleneck": 1,   # nn/layers/bottleneck.py fused chain
    "train_stem": 1,         # nn/layers/stem.py space-to-depth stem
}

AUTOTUNE_DECISIONS = "dl4jtpu_autotune_decisions_total"
AUTOTUNE_CALIBRATIONS = "dl4jtpu_autotune_calibrations_total"


def _count(metric: str, domain: str, choice: str) -> None:
    """Best-effort telemetry — the decision beats the counter."""
    try:
        from deeplearning4j_tpu.monitoring.metrics import global_registry
        global_registry().counter(
            metric, "kernel-crossover autotune events",
            ("domain", "choice")).inc(domain=domain, choice=choice)
    except Exception:  # noqa: BLE001 — telemetry must not cost a decision
        pass


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


def default_path() -> str:
    """cwd first (a run can carry a local store), then the repo root
    where the committed store lives — the TPULINT_BASELINE resolution
    order."""
    for cand in (os.path.join(os.getcwd(), CROSSOVER_NAME),
                 os.path.join(_repo_root(), CROSSOVER_NAME)):
        if os.path.exists(cand):
            return cand
    return os.path.join(_repo_root(), CROSSOVER_NAME)


def fingerprint(domain: str, dtype: Any = None, **dims: Any) -> str:
    """Stable human-readable entry key: ``domain|k=v,...|dtype``. Dims
    sort by name so call sites can't produce two spellings of one shape;
    the batch dimension is deliberately NOT part of the key (entries
    describe the per-shape crossover at the calibration batch — keys
    must survive the caller's batch choice, PERF.md round-3 A/Bs showed
    the verdict stable across B=64..256)."""
    dt = "any" if dtype is None else str(dtype)
    dt = {"bfloat16": "bf16", "float32": "f32", "float64": "f64"}.get(dt, dt)
    body = ",".join(f"{k}={dims[k]}" for k in sorted(dims))
    return f"{domain}|{body}|{dt}"


def bottleneck_fingerprint(h: int, w: int, c_in: int, c_mid: int,
                           c_out: int, stride: int, has_skip: bool,
                           dtype: Any) -> str:
    return fingerprint("train_bottleneck", dtype, h=int(h), w=int(w),
                       cin=int(c_in), cmid=int(c_mid), cout=int(c_out),
                       stride=int(stride), skip=int(bool(has_skip)))


def stem_fingerprint(h: int, w: int, c_in: int, c_out: int,
                     dtype: Any) -> str:
    return fingerprint("train_stem", dtype, h=int(h), w=int(w),
                       cin=int(c_in), cout=int(c_out))


def winner(entry: dict) -> str:
    """The ONE place the kernel-vs-fallback verdict rule lives:
    'kernel' iff the measured kernel time beats the fallback. choose(),
    record() telemetry, and every bench record derive from this."""
    return ("kernel" if entry.get("kernel_ms", float("inf"))
            < entry.get("fallback_ms", 0.0) else "fallback")


def _current_platform() -> str:
    import jax
    return jax.default_backend()


def _current_device_kind() -> str:
    import jax
    return jax.devices()[0].device_kind


class KernelCrossoverStore:
    """Load → consult → ratchet (the TPULINT_BASELINE lifecycle) for
    measured kernel-vs-fallback timings. Thread-safe: ``choose`` is on
    fit's resolution path."""

    def __init__(self, path: Optional[str] = None,
                 entries: Optional[Dict[str, dict]] = None):
        self.path = path or default_path()
        self._entries: Dict[str, dict] = dict(entries or {})
        self._lock = threading.Lock()
        self._warned: set = set()

    # -- persistence ---------------------------------------------------
    @classmethod
    def load(cls, path: Optional[str] = None) -> "KernelCrossoverStore":
        path = path or default_path()
        entries: Dict[str, dict] = {}
        if os.path.exists(path):
            try:
                with open(path, "r", encoding="utf-8") as f:
                    data = json.load(f)
                entries = dict(data.get("entries", {}))
            except (OSError, ValueError) as e:
                # a torn/garbled store must not take down a fit loop —
                # behave as uncalibrated and say why
                log.warning("kernel-crossover store %s unreadable (%s): "
                            "running uncalibrated", path, e)
                entries = {}
        store = cls(path=path, entries=entries)
        stale = store.prune_stale()
        if stale:
            log.info("kernel-crossover store: pruned %d stale entries "
                     "(impl revision changed): %s", len(stale),
                     ", ".join(sorted(stale)[:5]))
        return store

    def save(self, path: Optional[str] = None) -> str:
        """Atomic write (tmp + rename) — a crash mid-save must not leave
        future runs resolving from a torn store."""
        path = path or self.path
        with self._lock:
            payload = {"version": CROSSOVER_VERSION,
                       "tool": "kernel-crossover",
                       "entries": dict(sorted(self._entries.items()))}
        tmp = path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        return path

    # -- accounting ----------------------------------------------------
    def __len__(self) -> int:
        return len(self._entries)

    def entries(self) -> Dict[str, dict]:
        with self._lock:
            return {k: dict(v) for k, v in self._entries.items()}

    def prune_stale(self) -> list:
        """Drop entries whose recorded ``impl_rev`` no longer matches the
        current kernel revision for their domain (IMPL_REVS) — old
        timings describe a kernel that no longer exists."""
        dropped = []
        with self._lock:
            for key in list(self._entries):
                domain = key.split("|", 1)[0]
                rev = self._entries[key].get("impl_rev")
                if rev != IMPL_REVS.get(domain, rev):
                    dropped.append(key)
                    del self._entries[key]
        return dropped

    # -- consult -------------------------------------------------------
    def lookup(self, key: str) -> Optional[dict]:
        """The entry for ``key`` iff it was measured on THIS platform +
        device kind; a mismatched entry is ignored with a (once-per-key)
        warning — a CPU-calibrated store must never decide a TPU run,
        and v5e timings don't transfer to v4."""
        with self._lock:
            e = self._entries.get(key)
        if e is None:
            return None
        plat, kind = _current_platform(), _current_device_kind()
        if e.get("platform") != plat or (
                e.get("device_kind") not in (kind, "any")):
            if key not in self._warned:
                self._warned.add(key)
                log.warning(
                    "kernel-crossover entry %s was calibrated on %s/%s "
                    "but this run is %s/%s — ignoring it (recalibrate "
                    "on this hardware)", key, e.get("platform"),
                    e.get("device_kind"), plat, kind)
            return None
        return dict(e)

    def choose(self, key: str, default: Optional[str] = None
               ) -> Optional[str]:
        """'kernel' or 'fallback' from a usable calibrated entry, else
        ``default`` (the caller's static behavior — uncalibrated runs
        are unchanged by construction). Counts the decision."""
        domain = key.split("|", 1)[0]
        e = self.lookup(key)
        if e is None or not e.get("kernel_ms") or not e.get("fallback_ms"):
            _count(AUTOTUNE_DECISIONS, domain, "default")
            return default
        choice = winner(e)
        _count(AUTOTUNE_DECISIONS, domain, choice)
        return choice

    # -- ratchet -------------------------------------------------------
    def record(self, key: str, kernel_ms: float, fallback_ms: float, *,
               platform: Optional[str] = None,
               device_kind: Optional[str] = None,
               source: str = "record") -> dict:
        """Merge one paired measurement (running mean over samples —
        repeated calibrations ratchet toward the stable verdict instead
        of thrashing on run-to-run spread). Returns the merged entry."""
        kernel_ms = float(kernel_ms)
        fallback_ms = float(fallback_ms)
        if kernel_ms <= 0 or fallback_ms <= 0:
            raise ValueError(
                f"timings must be positive, got kernel={kernel_ms} "
                f"fallback={fallback_ms} for {key}")
        domain = key.split("|", 1)[0]
        plat = platform or _current_platform()
        kind = device_kind or _current_device_kind()
        with self._lock:
            e = self._entries.get(key)
            if (e is None or e.get("platform") != plat
                    or e.get("device_kind") != kind
                    or e.get("impl_rev") != IMPL_REVS.get(domain)):
                # fresh hardware or fresh kernel revision: start over
                e = {"kernel_ms": kernel_ms, "fallback_ms": fallback_ms,
                     "platform": plat, "device_kind": kind,
                     "impl_rev": IMPL_REVS.get(domain), "samples": 1,
                     "source": source}
            else:
                n = int(e.get("samples", 1))
                e = dict(e)
                e["kernel_ms"] = round(
                    (e["kernel_ms"] * n + kernel_ms) / (n + 1), 6)
                e["fallback_ms"] = round(
                    (e["fallback_ms"] * n + fallback_ms) / (n + 1), 6)
                e["samples"] = n + 1
                e["source"] = source
            self._entries[key] = e
        _count(AUTOTUNE_CALIBRATIONS, domain, winner(e))
        return dict(e)

    # -- measurement harness ------------------------------------------
    def calibrate(self, key: str, kernel_fn: Callable[[], Any],
                  fallback_fn: Callable[[], Any], *, warmup: int = 2,
                  iters: int = 5, persist: bool = False) -> dict:
        """Time the two thunks back to back (same-moment paired
        comparison — the only kind run-to-run spread permits, PERF.md)
        and record the result. Thunks must return their device output;
        the harness blocks on it so async dispatch can't flatter either
        side. ``persist=True`` saves the store after recording."""
        k_ms = _time_thunk(kernel_fn, warmup, iters)
        f_ms = _time_thunk(fallback_fn, warmup, iters)
        entry = self.record(key, k_ms, f_ms, source="calibrate")
        if persist:
            self.save()
        return entry


def _time_thunk(fn: Callable[[], Any], warmup: int, iters: int) -> float:
    """Mean ms per call, synced via block_until_ready on the thunk's
    output (tests monkeypatch this to decouple the harness from wall
    time)."""
    import jax
    out = None
    for _ in range(max(0, warmup)):
        out = fn()
    if out is not None:
        jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(max(1, iters)):
        out = fn()
    if out is not None:
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) * 1000.0 / max(1, iters)


_default_store: Optional[KernelCrossoverStore] = None
_default_lock = threading.Lock()


def default_store() -> KernelCrossoverStore:
    """Process-wide store singleton, loaded from the committed
    KERNEL_CROSSOVER.json on first use (resolution paths must not
    re-read the file per fit/engine construction)."""
    global _default_store
    with _default_lock:
        if _default_store is None:
            _default_store = KernelCrossoverStore.load()
        return _default_store


def reset_default_store(store: Optional[KernelCrossoverStore] = None
                        ) -> None:
    """Swap (or clear) the process singleton — tests and calibration
    runs point resolution at a scratch store."""
    global _default_store
    with _default_lock:
        _default_store = store
