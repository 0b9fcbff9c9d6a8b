"""Device busy time inside prefill dispatches per 1,000 prompt tokens fed,
over the primes the trace holds whole: numerator and denominator are the
same dispatches, wherever the trace was cut.

A prime is whole in the trace when its first ``prefill.input`` span and
its ``prefill.fetch`` span both closed inside it. Which request it served
the trace does not say, and its clock counts from its own first event,
while a request's ``prefill_start`` / ``prefill_end`` records are on the
host's. Both are stamped around the same call, a millisecond apart at
most, and primes are serial and last 0.3 to 1 s: so the one offset between
the clocks is sought under which the most whole primes start and end where
some request's records say (of equals, the closest fit). The requests so
found give the tokens fed (the record's ``width``: what a prefix hit
served is not in it), the programs ``rnn_time_step`` ran inside their
primes the device time. A cell whose primes take a fifth of the trace each
(``prefill.device_ms_per_ktok`` divides everything the trace holds by the
prompts that finished after its first second) reads 1.8 times high
there."""

from benchmark.metrics._common import STREAM_FWD
from benchmark.metrics._spans import span_intervals

#: how far a span's end may lie from the record stamped beside it, seconds
STAMP_TOLERANCE_S = 0.005


def whole_primes(trace):
    """``(start, end)`` of every prime whose first input span and fetch
    span the trace holds (a chunked prime has an input span a chunk)."""
    marks = sorted([(a, b, True) for a, b in
                    span_intervals(trace, ("prefill.input",))]
                   + [(a, b, False) for a, b in
                      span_intervals(trace, ("prefill.fetch",))])
    out, start = [], None
    for a, b, is_input in marks:
        if is_input:
            start = a if start is None else start
        elif start is not None:
            out.append((start, b))
            start = None
    return out


def primed_requests(replay):
    """``(prefill_start, prefill_end, tokens fed)`` of every request's
    first prime, on the host's clock."""
    out = []
    for r in replay.requests:
        start = None
        for ev in r.handle.trace().events():
            if ev["event"] == "prefill_start" and start is None:
                start = ev
            elif ev["event"] == "prefill_end" and start is not None:
                out.append((start["t"], ev["t"], start["width"]))
                break
    return out


def matched(primes, requests):
    """``[(prime, tokens fed)]`` under the offset that fits best."""
    best, best_key = [], (0, 0.0)
    for a0, _ in primes:
        for s0, _, _ in requests:
            offset = s0 - a0
            found, miss = [], 0.0
            for a, b in primes:
                fit = min((abs(s - a - offset) + abs(e - b - offset), fed)
                          for s, e, fed in requests)
                if fit[0] <= 2 * STAMP_TOLERANCE_S:
                    found.append(((a, b), fit[1]))
                    miss += fit[0]
            key = (len(found), -miss)
            if key > best_key:
                best, best_key = found, key
    return best


def read(ctx):
    trace = ctx["trace"]
    pairs = matched(whole_primes(trace),
                    primed_requests(ctx["record"]["serve"]["replay"]))
    tokens = sum(fed for _, fed in pairs)
    runs = [(a, b) for a, b in trace.module_runs(STREAM_FWD)
            if any(s <= a <= e for (s, e), _ in pairs)]
    if not tokens or not runs:
        return None
    return trace.busy_within(runs) * 1e3 / (tokens / 1000.0)
