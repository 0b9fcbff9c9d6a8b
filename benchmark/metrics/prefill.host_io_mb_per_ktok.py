"""Bytes a prime moves across the host boundary (prompt tensor up, result
down) per 1,000 prompt tokens fed."""
from benchmark.metrics._spans import health_delta, host_io_bytes


def read(ctx):
    nbytes = host_io_bytes(ctx, ("prefill",))
    fed = health_delta(ctx, "prefill", "fed_tokens")
    if nbytes is None or not fed:
        return None
    return nbytes / fed * 1000.0 / 1e6
