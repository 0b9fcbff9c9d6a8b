"""Share of the dispatched prefill positions that are padding: 1 - tokens
fed over padded widths, from ``health()["prefill"]`` at both ends."""
from benchmark.metrics._spans import health_delta


def read(ctx):
    fed = health_delta(ctx, "prefill", "fed_tokens")
    width = health_delta(ctx, "prefill", "bucket_tokens")
    if fed is None or not width:
        return None
    return 100.0 * (1.0 - fed / width)
