"""Device busy time inside prefill dispatches per 1,000 prompt tokens, in
the traced interval."""
from benchmark.metrics._common import traced_prefill


def read(ctx):
    busy, reqs = traced_prefill(ctx)
    tokens = sum(len(r.prompt) for r in reqs)
    if not busy or not tokens:
        return None
    return busy * 1e3 / (tokens / 1000.0)
