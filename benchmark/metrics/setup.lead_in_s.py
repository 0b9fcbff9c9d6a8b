"""Seconds from the engine's start to the window's opening: the lead-in,
the primes that bring every client its first token. From
``health()["setup"]["started_at"]`` as the window opened and the replay's
``t0``, both on ``time.perf_counter()``. A training cell has no engine."""


def read(ctx):
    if not ctx.get("trace_interval") or "serve" not in ctx["record"]:
        return None
    serve = ctx["record"]["serve"]
    started = serve["health0"].get("setup", {}).get("started_at")
    if started is None:
        return None
    return serve["replay"].t0 - started
