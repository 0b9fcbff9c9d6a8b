"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read: device busy time (union of op intervals), idle
gaps named by what the host was doing, time per device op, executions of
each compiled program.

    python3 benchmark/xplane.py <file.xplane.pb>     # what is in a trace

Reads the file with ``jax.profiler.ProfileData`` and nothing else. All
times are seconds. One ``Trace`` holds one traced window.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: host events that only say "a thread is waiting / the profiler is on":
#: an idle gap is named by work, not by a thread that sleeps through it
HOST_NOISE = ("ThreadpoolListener", "$profiler", "start_trace",
              "stop_trace", "ProfilerSession", "time sleep", "time.sleep",
              "threading.py", "$queue.py", "futex", "Wait")
#: a device op's event name is its HLO text: "%fusion.3 = bf16[8,128]{...}
#: fusion(...)"; the name and the result's type and dimensions are kept
HLO_OP = re.compile(r"^%?([\w.\-]+) = \(?(\w+)\[([\d,]*)\]")

Interval = Tuple[float, float]


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def union_length(intervals: List[Interval]) -> float:
    total, end = 0.0, -float("inf")
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def merged(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def short_op(name: str) -> str:
    """``%fwd.30 = bf16[32,2,12,128]{3,2,1,0:T(8,128)} custom-call(...)``
    -> ``fwd.30_bf16_32_2_12_128`` (``_custom-call`` kept: kernels are
    told from fusions by it); other names pass."""
    m = HLO_OP.match(name)
    if not m:
        return name
    dims = m.group(3).replace(",", "_")
    tail = "_custom-call" if " custom-call(" in name else ""
    return f"{m.group(1)}_{m.group(2)}_{dims}{tail}"


def clean(name: str) -> str:
    """A name the contract allows in ``breakdown``: no space, comma or
    slash."""
    return re.sub(r"[^A-Za-z0-9_.:-]+", "_", name).strip("_")[:64] or "_"


class Trace:
    """Events of one traced window, by plane.

    ``ops[device]`` / ``modules[device]``: ``(name, start, end)`` on the
    device's op and program lines; ``host``: ``(name, start, end)`` of
    host-thread events (profiler bookkeeping dropped)."""

    def __init__(self, ops, modules, host):
        self.ops: Dict[int, List[Tuple[str, float, float]]] = ops
        self.modules: Dict[int, List[Tuple[str, float, float]]] = modules
        self.host: List[Tuple[str, float, float]] = host

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        data = ProfileData.from_file(path)
        raw_ops, raw_modules, raw_host = {}, {}, []
        for plane in data.planes:
            m = DEVICE_PLANE.match(plane.name)
            if m:
                dev = int(m.group(1))
                for line in plane.lines:
                    if line.name not in (OPS_LINE, MODULES_LINE):
                        continue
                    evs = [(short_op(e.name), e.start_ns, e.duration_ns)
                           for e in line.events]
                    (raw_ops if line.name == OPS_LINE
                     else raw_modules)[dev] = evs
            elif plane.name.startswith("/host:CPU"):
                for line in plane.lines:
                    for e in line.events:
                        if e.duration_ns <= 0 or \
                                any(n in e.name for n in HOST_NOISE):
                            continue
                        raw_host.append((e.name, e.start_ns, e.duration_ns))
        # times count from the first event: as seconds since the epoch a
        # double resolves a quarter of a microsecond, less than an op lasts
        every = [e for evs in raw_ops.values() for e in evs] + \
            [e for evs in raw_modules.values() for e in evs] + raw_host
        base = min((e[1] for e in every), default=0.0)

        def rebased(evs):
            return [(n, (s - base) * 1e-9, (s - base) * 1e-9 + d * 1e-9)
                    for n, s, d in evs]

        return cls({d: rebased(v) for d, v in raw_ops.items()},
                   {d: rebased(v) for d, v in raw_modules.items()},
                   rebased(raw_host))

    @classmethod
    def from_dir(cls, trace_dir: str) -> "Trace":
        return cls.from_file(find_xplane(trace_dir))

    # -- window ---------------------------------------------------------
    def devices(self) -> List[int]:
        return sorted(self.ops)

    def span(self) -> Interval:
        """First device-op start to last device-op end, over all devices:
        the window the device numbers are taken over."""
        starts = [e[1] for evs in self.ops.values() for e in evs]
        ends = [e[2] for evs in self.ops.values() for e in evs]
        if not starts:
            raise ValueError("the trace holds no device operation")
        return min(starts), max(ends)

    def window_s(self) -> float:
        a, b = self.span()
        return b - a

    def busy_s(self, device: Optional[int] = None) -> float:
        """Seconds in which an operation ran: the union of the op
        intervals, averaged over the devices traced (or of one)."""
        devs = self.devices() if device is None else [device]
        return sum(union_length([(a, b) for _, a, b in self.ops[d]])
                   for d in devs) / len(devs)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s()

    # -- ops ------------------------------------------------------------
    def op_seconds(self, device: Optional[int] = None) -> Dict[str, float]:
        """Summed duration per op name on one device (default: the first)."""
        dev = self.devices()[0] if device is None else device
        out: Dict[str, float] = defaultdict(float)
        for name, a, b in self.ops[dev]:
            out[name] += b - a
        return dict(out)

    def seconds_matching(self, pattern: str,
                         device: Optional[int] = None) -> float:
        rx = re.compile(pattern)
        return sum(s for n, s in self.op_seconds(device).items()
                   if rx.search(n))

    def count_matching(self, pattern: str,
                       device: Optional[int] = None) -> int:
        dev = self.devices()[0] if device is None else device
        rx = re.compile(pattern)
        return sum(1 for n, _, _ in self.ops[dev] if rx.search(n))

    def module_runs(self, pattern: str, device: Optional[int] = None
                    ) -> List[Interval]:
        """Executions of the compiled programs whose name matches."""
        dev = self.devices()[0] if device is None else device
        rx = re.compile(pattern)
        return [(a, b) for n, a, b in self.modules.get(dev, [])
                if rx.search(n)]

    def busy_within(self, spans: List[Interval],
                    device: Optional[int] = None) -> float:
        """Device-op time that falls inside the given spans."""
        dev = self.devices()[0] if device is None else device
        spans = merged(spans)
        total, i = 0.0, 0
        for a, b in merged([(a, b) for _, a, b in self.ops[dev]]):
            while i < len(spans) and spans[i][1] <= a:
                i += 1
            j = i
            while j < len(spans) and spans[j][0] < b:
                total += max(0.0, min(b, spans[j][1]) - max(a, spans[j][0]))
                j += 1
        return total

    # -- breakdown ------------------------------------------------------
    def top_ops(self, n: int = 10) -> List[List]:
        secs = self.op_seconds()
        return [[clean(k), v] for k, v in
                sorted(secs.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10, min_gap_s: float = 20e-6) -> List[List]:
        """Idle time of the first device, attributed to the host event
        that covers most of each gap (the innermost such event), summed
        by name."""
        dev = self.devices()[0]
        busy = merged([(a, b) for _, a, b in self.ops[dev]])
        gaps = [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)
                if busy[i + 1][0] - busy[i][1] >= min_gap_s]
        host = sorted(self.host, key=lambda e: e[1])
        starts = [e[1] for e in host]
        longest = max((b - a for _, a, b in host), default=0.0)
        by_name: Dict[str, float] = defaultdict(float)
        for ga, gb in gaps:
            best, best_key = None, (0.0, 0.0)
            lo = bisect.bisect_left(starts, ga - longest)
            hi = bisect.bisect_right(starts, gb)
            for name, a, b in host[lo:hi]:
                ov = min(b, gb) - max(a, ga)
                if ov <= 0:
                    continue
                # most overlap first; among equals the shorter (inner) one
                key = (round(ov / (gb - ga), 2), -(b - a))
                if key > best_key:
                    best, best_key = name, key
            by_name[best or "no_host_event"] += gb - ga
        return [[clean(k), v] for k, v in
                sorted(by_name.items(), key=lambda kv: -kv[1])[:n]]

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def describe(path: str, top: int = 25) -> None:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            tot = sum(e.duration_ns for e in evs) * 1e-9
            print(f"  LINE {line.name!r}: {len(evs)} events, {tot:.4f} s")
            secs: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
            for e in evs:
                secs[e.name][0] += 1
                secs[e.name][1] += e.duration_ns * 1e-9
            for name, (cnt, s) in sorted(secs.items(),
                                         key=lambda kv: -kv[1][1])[:top]:
                print(f"      {s:10.6f} s  x{cnt:<6d} {name[:100]}")
    tr = Trace.from_file(path)
    if tr.ops:
        print("window_s", tr.window_s(), "busy_s", tr.busy_s(),
              "idle_share", tr.idle_share())
        print("idle gaps:", tr.idle_gaps())


if __name__ == "__main__":
    describe(sys.argv[1])
