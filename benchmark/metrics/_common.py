"""Helpers the per-layer readers share. A reader takes ``ctx`` (the cell,
its configuration and traffic, the run's record, the reduced trace, the
peaks) and returns a number, or ``None`` when it finds nothing to read."""

import re
import statistics

#: the compiled train step (``ComputationGraph._get_train_step``)
TRAIN_STEP = r"^jit_step"
#: ``rnn_time_step``'s compiled forward: the decode dispatch and every
#: prefill bucket are programs of this one name
STREAM_FWD = r"^jit_fwd"
#: the Mosaic paged-attention kernel's ops inside the decode program
PAGED_KERNEL = r"^fwd(\.\d+)?_.*_custom-call$"


def stream_programs(trace):
    """``rnn_time_step`` executions on the first device, split into the
    decode dispatch (the program that ran most often) and the prefill
    buckets (the others). Returns ``(decode_runs, prefill_runs)``.

    The program names none of them (PERF.md, Open questions), so the
    split stands on the decode dispatch running far more often than any
    one prefill bucket. Where no program ran over twice as often as the
    runner-up, the split is a guess, and that is an error."""
    dev = trace.devices()[0]
    rx = re.compile(STREAM_FWD)
    by_name = {}
    for name, a, b in trace.modules.get(dev, []):
        if rx.search(name):
            by_name.setdefault(name, []).append((a, b))
    if not by_name:
        return [], []
    ranked = sorted(by_name, key=lambda n: len(by_name[n]), reverse=True)
    decode = ranked[0]
    if len(ranked) > 1 and \
            len(by_name[decode]) <= 2 * len(by_name[ranked[1]]):
        raise RuntimeError(
            "cannot tell the decode dispatch from the prefill buckets: "
            + ", ".join(f"{n} x{len(by_name[n])}" for n in ranked[:3])
            + "; no one program ran over twice as often as the next")
    prefill = [r for n, runs in by_name.items() if n != decode
               for r in runs]
    return by_name[decode], prefill


def requests_with_first_token_in(ctx, interval):
    replay = ctx["record"]["serve"]["replay"]
    a, b = interval
    return [r for r in replay.requests
            if r.token_t and a <= r.token_t[0] <= b]


def decode_contexts_in(ctx, interval):
    """Number of keys each decode token stamped in ``interval`` saw: the
    j-th generated token (j >= 1) of a prompt of p tokens attends p + j
    keys (the first token comes from the prefill)."""
    replay = ctx["record"]["serve"]["replay"]
    a, b = interval
    out = []
    for r in replay.requests:
        p = len(r.prompt)
        for j, t in enumerate(r.token_t):
            if j >= 1 and a <= t <= b:
                out.append(p + j)
    return out


def median(values):
    values = list(values)
    return statistics.median(values) if values else None


def idle_share(ctx):
    """1 - union of device-op intervals over the traced window, in
    percent."""
    return 100.0 * ctx["trace"].idle_share()


def traced_prefill(ctx):
    """``(device seconds inside prefill dispatches, requests they served)``
    in the traced interval. Requests whose first token came in its first
    second are left out: their prefill may have run before the trace
    began."""
    _, prefill = stream_programs(ctx["trace"])
    a, b = ctx["trace_interval"]
    reqs = requests_with_first_token_in(ctx, (a + 1.0, b))
    busy = ctx["trace"].busy_within(prefill) if prefill else 0.0
    return busy, reqs
