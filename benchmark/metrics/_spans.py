"""Device-idle time split by the program's own phases. The serving engine
keeps its thread in exactly one ``monitoring`` span at a time while it has
work (flat: no span inside another); each lands in the trace's host events
under its name, on the device ops' clock. "Idle in" a set of spans is the
idle time of device 0 that falls inside them.

A span reaches the trace when it closes inside it: the one that was open
when the trace began, and the one open when it ended, are missing. Idle
time before the first program span's start or after the last one's end lay
in such a span, whose name the trace does not hold: it is "cut". Idle time
between the two and inside no span is "unnamed". The parts, the unnamed
rest and the cut ends add up to the idle time ``*.device_idle_share``
counts (first device op to last).

A trace of a program that emits none of these spans reads ``None``."""

from benchmark.xplane import merged

#: the engine cycle's phases, in cycle order (serving/engine.py, "Phases")
ADMISSION = ("engine.admit", "prefill.input", "prefill.forward",
             "prefill.fetch", "engine.seat")
DECODE_IO = ("decode.input", "decode.forward", "decode.fetch")
SAMPLE = ("engine.sample",)
PROGRAM_SPANS = ("engine.reap",) + ADMISSION + DECODE_IO + SAMPLE


def span_intervals(trace, names):
    """``(start, end)`` of every host event named in ``names``."""
    names = set(names)
    return [(a, b) for n, a, b in trace.host if n in names]


def idle_intervals(trace):
    """The gaps between device 0's operations, first op to last."""
    dev = trace.devices()[0]
    busy = merged([(a, b) for _, a, b in trace.ops[dev]])
    return [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)]


def overlap_s(xs, ys):
    """Seconds that lie in both sets of intervals."""
    xs, ys = merged(xs), merged(ys)
    total, j = 0.0, 0
    for a, b in xs:
        while j < len(ys) and ys[j][1] <= a:
            j += 1
        k = j
        while k < len(ys) and ys[k][0] < b:
            total += min(b, ys[k][1]) - max(a, ys[k][0])
            k += 1
    return total


def idle_in(trace, names):
    """Idle seconds inside the spans named, or ``None`` where the trace
    holds no span of the program at all."""
    if not span_intervals(trace, PROGRAM_SPANS):
        return None
    return overlap_s(idle_intervals(trace), span_intervals(trace, names))


def idle_shares(trace):
    """``(unnamed, cut)``: idle time inside no program span, and idle time
    outside the first-to-last program span, each over all idle time, in
    percent."""
    spans = span_intervals(trace, PROGRAM_SPANS)
    gaps = idle_intervals(trace)
    idle = sum(b - a for a, b in gaps)
    if not spans or idle <= 0:
        return None, None
    window = [(min(a for a, _ in spans), max(b for _, b in spans))]
    seen = overlap_s(gaps, window)
    return (100.0 * (seen - overlap_s(gaps, spans)) / idle,
            100.0 * (idle - seen) / idle)


def idle_unnamed_share(trace):
    return idle_shares(trace)[0]


def idle_cut_share(trace):
    return idle_shares(trace)[1]


def idle_ms_per(trace, names, per):
    """Idle milliseconds inside ``names`` per span named ``per``. A decode
    cycle has one ``decode.forward``; a phase of a prime is divided by its
    own spans, so that a prime the trace cut counts where it was seen."""
    n = len(span_intervals(trace, (per,)))
    idle = idle_in(trace, names)
    if idle is None or not n:
        return None
    return idle / n * 1e3


def health_delta(ctx, *path):
    """A counter of ``engine.health()`` over the window (both ends are in
    the run's record), or ``None`` where the program has no such key."""
    ends = []
    for end in ("health0", "health1"):
        v = ctx["record"]["serve"][end]
        for k in path:
            if not isinstance(v, dict) or k not in v:
                return None
            v = v[k]
        ends.append(v)
    return ends[1] - ends[0]


def host_io_bytes(ctx, phases=("decode", "prefill")):
    parts = [health_delta(ctx, "host_io", p, d)
             for p in phases for d in ("h2d_bytes", "d2h_bytes")]
    return None if None in parts else sum(parts)
