"""Device-idle time while a prime's result comes back (``prefill.fetch``)
per prime in the trace."""
from benchmark.metrics._spans import idle_ms_per


def read(ctx):
    return idle_ms_per(ctx["trace"], ("prefill.fetch",), "prefill.fetch")
